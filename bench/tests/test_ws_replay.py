"""Self-test of the weight-sharing replay entry on the CPU at a tiny size.

In a temporary copy of the benchmark it adds a small configuration under
the learner, a mix for the ``ws_replay`` entry and their cell, runs the cell
and sees it correct, with the learner's per-layer metric in a traced run;
then plants each fault of ``ws_plants.py`` in the timed path and sees
``correct`` come out false.

  python -m pytest bench/tests -q        # from the repository root
"""
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

from ws_plants import FAULTS  # noqa: E402

CELL = "tiny_ws.ws_replay"
# 30,000 requests over 3,000 pages in 4 shards of 96 lines: tier 1 holds
# about a third of a shard's pages, so most misses evict and the learner
# moves its weights.
TINY_WS = {
    "name": "tiny_ws",
    "source": "a 30,000-request IRM trace under the learner, for the "
              "self-test",
    "stream": {"kind": "irm", "n_requests": 30000, "n_pages": 3000,
               "zipf_s": 0.99, "scramble": "fnv1a64", "write_fraction": 0.0,
               "rate": 1600.0},
    "store": {"n_shards": 4, "mapping": "block", "n_lines": 96,
              "policy": "ws", "prefetch": False, "epoch_width": 4,
              "alpha": 0.5, "beta": 0.7, "threshold": 0.25},
    "windows": {"n_windows": 8},
    "reduced": [],
}
MIX = {"entry": "ws_replay", "rate_metric": "replay_requests_per_s",
       "chunk": 4096, "slice_chunks": 1, "trace_from_query": 1,
       "trace_seconds": 0.5}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    co = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co)
    shutil.copytree(BENCH, os.path.join(co, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = os.path.join(co, "bench")
    with open(os.path.join(b, "configs", "tiny_ws.json"), "w") as f:
        json.dump(TINY_WS, f)
    with open(os.path.join(b, "traffic", "tiny_ws_replay.json"), "w") as f:
        json.dump(MIX, f)
    path = os.path.join(co, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny_ws", "source": TINY_WS["source"],
                             "file": "bench/configs/tiny_ws.json",
                             "reduced": [], "why": "self-test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_ws",
                               "traffic": "tiny_ws_replay", "chips": 1,
                               "why": "self-test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "wl1_p16.ws_replay" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(co, "src"))
    return co


def run(co, trace=0, plant=None):
    if plant is None:
        cmd = [os.path.join(co, "bench", "tests", "cpu_run.py"), co]
    else:
        cmd = [os.path.join(co, "bench", "tests", "ws_plants.py"), co,
               "--plant", plant]
    cmd += ["--workload", CELL, "--seed", str(2**33 + 7), "--seconds", "2",
            "--trace", str(trace)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable] + cmd, capture_output=True,
                       text=True, env=env, cwd=co, timeout=600)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 0 and lines and lines[-1].startswith("{"), (
        p.stderr[-3000:])
    return json.loads(lines[-1]), p.stderr


def test_ws_cell_is_correct(checkout):
    res, err = run(checkout)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"replay_requests_per_s", "setup_s"}
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert "largest weight difference" in err


def test_ws_cell_traced_reads_the_learner_metric(checkout):
    res, _ = run(checkout, trace=1)
    assert res["correct"] is True
    names = {"chunk_engine_ms.replay", "chunk_host_ms.replay",
             "resume_prep_ms_per_slice.replay",
             "scan_steps_per_request.replay",
             "engine_us_per_eviction.ws_replay"}
    assert names <= set(res["metrics"])
    assert res["metrics"]["engine_us_per_eviction.ws_replay"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_reads_incorrect(checkout, fault):
    res, _ = run(checkout, plant=fault)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
