"""Harness self-test on the CPU at a tiny size.

In a temporary copy of the benchmark it adds two configuration files, three
traffic files, a metric file and their ``BENCHMARK.json`` entries, checks
that no file already there changed, and runs the new cells through
``cpu_run.py``. It then plants each fault of ``plants.py`` in the timed
path and sees ``correct`` come out false, and sees a run with no TPU, and a
checkout without the program, exit non-zero with no result line.

  python -m pytest bench/tests -q        # from the repository root
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, TESTS)

from plants import FAULTS  # noqa: E402

TINY = {
    "name": "tiny",
    "source": "a 32,768-request IRM trace for the self-test",
    # Every chunk's fullest shard (the one with the most popular page, ~35 %
    # of a chunk) fits the primary length bucket, so one bucket serves all.
    "stream": {"kind": "irm", "n_requests": 32768, "n_pages": 2048,
               "zipf_s": 1.1, "scramble": "fnv1a64", "write_fraction": 0.0,
               "rate": 1600.0},
    "store": {"n_shards": 4, "mapping": "block", "n_lines": 128,
              "policy": "lru", "prefetch": False},
    "windows": {"n_windows": 8},
    "reduced": [],
}
TINY_OL = {
    "name": "tiny_ol",
    "source": "a 2,000-request Poisson decay stream with faults, for the "
              "self-test",
    "stream": {"kind": "poisson_decay", "n_requests": 2000, "n_pages": 512,
               "decay_tau": 200.0, "arrival_rate": 0.05,
               "write_fraction": 0.0, "rate": 240.0},
    "store": {"n_shards": 4, "mapping": "round_robin", "n_lines": 16,
              "policy": "lru", "prefetch": False},
    "windows": {"n_windows": 32, "window_dt": 0.3},
    "faults": {"shard_down": [{"shard": 1, "t0": 0.8, "t1": 2.4}],
               "device_degrade": [{"tier": 2, "factor": 0.4, "t0": 1.5,
                                   "t1": 4.0}],
               "retry": {"timeout": 0.05, "max_retries": 2,
                         "backoff_init": 0.4}},
    "reduced": [],
}
CONFIGS = {"tiny": TINY, "tiny_ol": TINY_OL}
MIXES = {
    "tiny_replay": {"entry": "replay", "rate_metric": "replay_requests_per_s",
                    "chunk": 4096, "slice_chunks": 2,
                    "trace_from_query": 1, "trace_seconds": 0.5},
    "tiny_curve": {"entry": "mrc_curve", "rate_metric": "mrc_requests_per_s",
                   "sizes": {"first": 32, "step": 32, "count": 8},
                   "stream_seed": 2**35 + 1},
    "tiny_sweep": {"entry": "knob_sweep", "rate_metric": "sweep_points_per_s",
                   "axes": {"store.alpha": [0.2, 0.8],
                            "store.policy": ["lru", "lfu"]}},
}
METRIC = '''"""Chunks the program replayed per query (a program counter)."""


def read(ctx):
    return ctx.profile.get("stream_chunks", 0) / ctx.queries or None
'''
CELLS = {"tiny.replay": ("tiny", "tiny_replay"),
         "tiny.curve": ("tiny", "tiny_curve"),
         "tiny_ol.sweep": ("tiny_ol", "tiny_sweep")}


def digest(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "src")]
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def add_cells(co):
    """Everything a later change adds for a new cell: files and entries."""
    b = os.path.join(co, "bench")
    for name, cfg in CONFIGS.items():
        with open(os.path.join(b, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in MIXES.items():
        with open(os.path.join(b, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(b, "metrics", "tiny_chunks_per_query.py"),
              "w") as f:
        f.write(METRIC)
    path = os.path.join(co, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        bench["configs"].append({"name": name, "source": cfg["source"],
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "self-test"})
    for cell, (config, mix) in CELLS.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": "self-test"})
        rate = MIXES[mix]["rate_metric"]
        for m in bench["end_to_end"] + bench["per_layer"]:
            if rate in (m["name"], m.get("moves")) and "workloads" in m:
                m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "tiny_chunks_per_query", "unit": "chunks", "better": "higher",
        "source": "program_counter", "layer": "self-test",
        "moves": "replay_requests_per_s", "workloads": ["tiny.replay"]})
    with open(path, "w") as f:
        json.dump(bench, f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    co = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co)
    shutil.copytree(BENCH, os.path.join(co, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(co)
    add_cells(co)
    after = digest(co)
    changed = [p for p in before if before[p] != after.get(p)
               and p != "BENCHMARK.json"]
    assert not changed, f"adding a cell edited {changed}"
    os.symlink(os.path.join(ROOT, "src"), os.path.join(co, "src"))
    return co


def run(co, *args, plant=None, script=None):
    cmd = [sys.executable]
    if script is None:
        cmd += [os.path.join(co, "bench", "tests", "cpu_run.py"), co]
        if plant:
            cmd += ["--plant", plant]
    else:
        cmd += [script]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(cmd + list(args), capture_output=True, text=True,
                       env=env, cwd=co, timeout=600)
    lines = p.stdout.strip().splitlines()
    result = None
    if lines and lines[-1].startswith("{"):
        result = json.loads(lines[-1])
    return p.returncode, result, p.stderr


def cell_args(cell, trace=0, seconds=3):
    return ["--workload", cell, "--seed", str(2**33 + 5), "--seconds",
            str(seconds), "--trace", str(trace)]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_new_cell_runs_and_is_correct(checkout, cell):
    rc, res, err = run(checkout, *cell_args(cell))
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    rate = MIXES[CELLS[cell][1]]["rate_metric"]
    assert set(res["metrics"]) == {rate, "setup_s"}
    assert res["metrics"][rate]["value"] > 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    assert "compiles in window: 0 " in err


def test_new_cell_traced_reads_its_metric(checkout):
    rc, res, err = run(checkout, *cell_args("tiny.replay", trace=1))
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert res["metrics"]["tiny_chunks_per_query"]["value"] == 2
    assert "replay_requests_per_s" not in res["metrics"]
    # The mix traces a 0.5 s sample from the start of the second query.
    assert res["device"]["window_s"] == pytest.approx(0.5, abs=0.05)
    assert "busy_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_sweep_cell_traced_reads_the_sweep_spans(checkout):
    rc, res, err = run(checkout, *cell_args("tiny_ol.sweep", trace=1))
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    names = {"stream_gen_s_per_point.sweep", "engine_submit_s_per_point.sweep",
             "engine_wait_s_per_point.sweep",
             "report_solve_s_per_point.sweep"}
    assert set(res["metrics"]) == names
    assert all(res["metrics"][n]["value"] > 0 for n in names)
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_planted_fault_reads_incorrect(checkout, cell, fault):
    rc, res, err = run(checkout, *cell_args(cell), plant=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_no_tpu_exits_without_result(checkout):
    rc, res, err = run(checkout, *cell_args("tiny.replay"),
                       script=os.path.join(checkout, "bench", "run.py"))
    assert rc != 0 and res is None
    assert "no TPU" in err


def test_checkout_without_program_exits_without_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, res, _ = run(str(tmp_path), *cell_args("wl2_p16.replay"))
    assert rc != 0 and res is None
