"""The control of a cell's correctness check: the plain reference put in the
program's place with one of the configuration's guarantees broken, compared
by the same comparison the benchmark makes. Its readings are the upper ends
the limits in ``bench/entries`` sit below.

  python bench/control.py --workload <cell> --seeds <n> [<n> ...]

Controls (``reference.counters``'s ``control``):

- ``fifo`` — a hit does not refresh recency (the LRU guarantee broken);
- ``reset`` — the replay does not carry its cache state across a resume
  (the checkpoint guarantee broken); replay cells only.

For a knob sweep ``fifo`` means a hit leaves its line's recency (LRU) or
count (LFU) as it was; its readings cover every point of one grid, over
the stream of a run's first query.

The replay control covers the requests a window consumes (``--slices``
slices of the mix); the curve control covers the answers a run's check
compares, every size once, on the curve of a run's first query. Host work only; it needs no chip.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import types

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

import adapters  # noqa: E402
import reference  # noqa: E402
from traffic_gen import make_trace, poisson_decay_trace  # noqa: E402


def as_program(ctl: dict, n_shards: int):
    """Control counters in the program's layout (``[S, ...]`` arrays); a
    shard the control did not compute reads zero."""
    out = types.SimpleNamespace()
    some = next(iter(ctl.values()))
    for name in reference.TOTALS + reference.WINDOWED + ("win_weights",):
        rows = [np.asarray(ctl[s][name]) if s in ctl
                else np.zeros_like(np.asarray(some[name]))
                for s in range(n_shards)]
        setattr(out, name, np.stack(rows))
    return out


def replay_readings(cfg, mix, seed, slices, controls=("fifo", "reset")):
    trace = make_trace(cfg["stream"], seed)
    step = int(mix["chunk"]) * int(mix["slice_chunks"])
    prefix = min(len(trace[0]), slices * step)
    resets = list(range(step, prefix, step))
    args = adapters.reference_args(cfg)
    ref = reference.counters(*trace, prefix=prefix, **args)
    out = {}
    for control in controls:
        ctl = reference.counters(*trace, prefix=prefix, control=control,
                                 resets=resets, **args)
        got = reference.mismatches(as_program(ctl, args["n_shards"]),
                                   ref)
        out[control] = {"counter_mismatches": got["counters"],
                        "weight_mismatches": got["weights"]}
    return out


def curve_readings(cfg, mix, seed, controls=("fifo",)):
    """Control readings over the answers a curve run with one query
    checks: every size once, on that query's rotation of the trace."""
    pages, is_write, times = adapters.curve_trace(cfg, mix)
    offset = adapters.curve_offset(seed, 0, len(pages))
    trace = (np.roll(pages, -offset), np.roll(is_write, -offset), times)
    args = adapters.reference_args(cfg)
    del args["n_lines"]
    pairs = [(shard, size) for _, size, shard in adapters.curve_checks(
        seed, 1, adapters.curve_sizes(mix), args["n_shards"])]
    ref = reference.pair_counters(*trace, pairs, **args)
    out = {}
    for control in controls:
        ctl = reference.pair_counters(*trace, pairs, control=control, **args)
        bad = {"counter_mismatches": 0, "weight_mismatches": 0}
        for shard, size in pairs:
            got = reference.mismatches(
                as_program({shard: ctl[(shard, size)]}, args["n_shards"]),
                {shard: ref[(shard, size)]})
            bad["counter_mismatches"] += got["counters"]
            bad["weight_mismatches"] += got["weights"]
        out[control] = bad
    return out


def as_report(ctl: dict):
    """Control counters as the program's report lays them out."""
    shards = [types.SimpleNamespace(**{n: ctl[s][n] for n in
                                       reference.REPORT_TOTALS})
              for s in sorted(ctl)]
    windows = {field: np.stack([ctl[s][name] for s in sorted(ctl)])
               for name, field in reference.REPORT_WINDOWED.items()}
    windows["weights"] = np.stack([ctl[s]["win_weights"]
                                   for s in sorted(ctl)])
    return types.SimpleNamespace(shards=shards,
                                 windows=types.SimpleNamespace(**windows))


def sweep_readings(cfg, mix, seed, controls=("fifo",)):
    trace = poisson_decay_trace(cfg["stream"],
                                adapters.sweep_traffic_seed(seed, 0))
    points = [dict(zip(mix["axes"], combo)) for combo in
              itertools.product(*mix["axes"].values())]
    out = {c: {"counter_mismatches": 0, "weight_mismatches": 0}
           for c in controls}
    for control in controls:
        for point in points:
            args = adapters.fault_reference_args(cfg, point["store.policy"])
            ref = reference.fault_counters(*trace, **args)
            ctl = reference.fault_counters(*trace, control=control, **args)
            got = reference.report_mismatches(as_report(ctl), ref)
            out[control]["counter_mismatches"] += got["counters"]
            out[control]["weight_mismatches"] += got["weights"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--slices", type=int, default=2)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg_file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_file)) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    for seed in args.seeds:
        if mix["entry"] == "replay":
            got = replay_readings(cfg, mix, seed, args.slices)
        elif mix["entry"] == "knob_sweep":
            got = sweep_readings(cfg, mix, seed)
        else:
            got = curve_readings(cfg, mix, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "controls": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
