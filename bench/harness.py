"""The benchmark harness: one cell of ``BENCHMARK.json``, set up, measured
for a window of queries, checked, and reported as one JSON line.

Everything that belongs to a cell is found by name: the configuration in
the file its ``configs`` entry names, the traffic mix in
``bench/traffic/<traffic>.json``, the code that drives the mix's entry in
``bench/entries/<entry>.py``, and each per-layer metric's reader in
``bench/metrics/<metric>.py``. Adding a cell, a mix, an entry or a metric
adds files and entries; no file here changes.

An entry module defines ``Entry(cfg, mix, seed, devices)``, whose
constructor makes the data and warms every shape; ``query(i)`` runs query
``i`` and returns the work it did; ``profile`` is the program's span and
counter dict the queries fill; and ``check()``, called once the window has
closed and the device's peak memory has been read, returns ``{name: (value, limit)}`` of the numbers compared with
the reference, each correct where ``value <= limit``.

A traced run (``--trace 1``) profiles the whole window, or, where the mix
sets ``trace_seconds``, a sample of that many seconds from the start of
query ``trace_from_query``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import threading
import time
import types
from typing import Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from trace_reduce import SAMPLE_START  # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_devices(chips: int):
    """The chips a cell runs on: TPUs only, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU found (JAX sees {devs[0].platform}); "
                         "this benchmark runs on a TPU only")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Tracer:
    """The profiler over the window, or over a sample of it: from the start
    of query ``from_query`` for ``seconds`` (the whole window when
    ``seconds`` is None). A sample is for cells whose device emits more
    trace events than a run can write and read in its time."""

    def __init__(self, directory: str, from_query: int = 0,
                 seconds: Optional[float] = None):
        self.directory, self.from_query, self.seconds = (
            directory, from_query, seconds)
        self.lock = threading.Lock()
        self.state = "idle"
        self.timer = None
        self.sample_s = None    # the sample's length on the host clock
        self.stop_s = None      # what stopping (and writing) the trace took

    def before_query(self, i: int) -> None:
        import jax
        if i != self.from_query or self.state != "idle":
            return
        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        self.state = "on"
        if self.seconds is not None:
            with jax.profiler.TraceAnnotation(SAMPLE_START):
                self.t_mark = time.perf_counter()
            self.timer = threading.Timer(self.seconds, self.stop)
            self.timer.start()

    def stop(self) -> None:
        import jax
        with self.lock:
            if self.state == "on":
                t = time.perf_counter()
                if self.seconds is not None:
                    self.sample_s = t - self.t_mark
                jax.profiler.stop_trace()
                self.stop_s = time.perf_counter() - t
                self.state = "done"

    def finish(self) -> None:
        if self.timer is not None:
            self.timer.join()
        self.stop()


def run_window(entry, seconds: float, tracer: Optional[Tracer] = None):
    """Queries back to back; the first that ends after ``seconds`` closes
    the window. Returns ``(queries, work, elapsed)``."""
    import jax
    queries = work = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            if tracer is not None:
                tracer.before_query(queries)
            with jax.profiler.TraceAnnotation("bench.query"):
                work += entry.query(queries)
            queries += 1
            if time.perf_counter() - t0 >= seconds:
                break
    elapsed = time.perf_counter() - t0
    if tracer is not None:
        tracer.finish()
    return queries, work, elapsed


def main(argv, root: str, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cell_of(bench, args.workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    from clock import CompileLog
    devices = find_devices(int(cell["chips"]))
    from repro.launch.compat import use_compile_cache
    cache = use_compile_cache(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileLog().listen()
    say(f"device: {devices[0].platform} {devices[0].device_kind} x"
        f"{len(devices)}; compile cache {cache}")

    entry_mod = load_module(os.path.join(BENCH, "entries",
                                         mix["entry"] + ".py"),
                            "bench_entry_" + mix["entry"])
    with jax.default_device(devices[0]):
        entry = entry_mod.Entry(cfg, mix, args.seed, devices)
        setup_s = time.perf_counter() - t_start
        say(f"setup_s: {setup_s}")
        trace_dir = os.path.join(root, "bench_out", "trace")
        tracer = None
        if args.trace:
            tracer = Tracer(trace_dir, int(mix.get("trace_from_query", 0)),
                            mix.get("trace_seconds"))
        w0 = time.perf_counter()
        queries, work, elapsed = run_window(entry, args.seconds, tracer)
        w1 = time.perf_counter()
        inside = compiles.between(w0, w1)
        say(f"compiles in window: {sum(inside['events'].values())} "
            f"{inside['events']} ({inside['seconds']} s)")
        say(f"window: {queries} queries, work {work}, {elapsed} s")
        peak = memory_peak(devices)

    summary = None
    if args.trace:
        from trace_reduce import summarize
        t = time.perf_counter()
        summary = summarize(trace_dir, tracer.sample_s)
        say(f"trace: stop and write {tracer.stop_s} s, read and reduce "
            f"{time.perf_counter() - t} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
    checks = entry.check()
    correct = all(v <= lim for v, lim in checks.values())

    e2e = {"setup_s": setup_s, mix["rate_metric"]: work / elapsed}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    reported = {m["name"] for m in bench["end_to_end"]
                if applies(m, cell["name"], set())}
    metrics = {}
    if args.trace:
        ctx = types.SimpleNamespace(profile=entry.profile, trace=summary,
                                    work=work, queries=queries,
                                    window_s=elapsed)
        for m in bench["per_layer"]:
            if not applies(m, cell["name"], reported):
                continue
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"),
                                 "bench_metric_" + m["name"])
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        for name in sorted(reported):
            metrics[name] = {"value": e2e[name], "unit": units[name]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": queries, "failed": 0,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        say(f"check {k}: {v} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0
