"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes them to repo-root
``BENCH_run.json`` (every benchmark artifact lands at the repo root as
``BENCH_<name>.json``). The dry-run / roofline cells (deliverables e+g) are
produced by ``python -m repro.launch.dryrun`` (long-running, writes
benchmarks/results/dryrun.json) and summarized here if that file exists.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import paper_tables as pt  # noqa: E402
from repro.launch.compat import use_compile_cache  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(ROOT, "BENCH_run.json")


def _dryrun_summary() -> list[tuple]:
    path = os.path.join(os.path.dirname(__file__), "results", "dryrun.json")
    if not os.path.exists(path):
        return [("dryrun", 0.0, "not-run (python -m repro.launch.dryrun)")]
    with open(path) as f:
        d = json.load(f)
    ok = sum(1 for r in d.values() if r.get("status") == "ok")
    sk = sum(1 for r in d.values() if r.get("status") == "skipped")
    er = sum(1 for r in d.values() if r.get("status") == "error")
    rows = [("dryrun_cells", 0.0, f"ok={ok};skipped={sk};error={er}")]
    for k in sorted(d):
        r = d[k]
        if r.get("status") == "ok" and k.endswith("pod1"):
            rows.append((
                f"roofline_{k[:-5]}", 0.0,
                f"dom={r['dominant']};frac={r['roofline_frac']:.3f};"
                f"tc={r['t_compute_s']:.3g};tm={r['t_memory_s']:.3g};"
                f"tcoll={r['t_collective_s']:.3g}"))
    return rows


def _report_summary() -> list[tuple]:
    """Batched report pipeline gates (benchmarks/bench_report.py)."""
    path = os.path.join(ROOT, "BENCH_report.json")
    if not os.path.exists(path):
        return [("bench_report", 0.0,
                 "not-run (python benchmarks/bench_report.py)")]
    with open(path) as f:
        d = json.load(f)
    eq, cg, sp = d["equivalence"], d["compile_gate"], d["speedup"]
    rows = [(
        "report_equivalence", 0.0,
        f"healthy_max_diff={eq['healthy_max_diff']:.2e};"
        f"bit_exact={eq['bit_exact_json']};ok={eq['ok']}"),
        ("report_compile_gate", 0.0,
         f"points={cg['n_points']};compiles={cg['compiles']};"
         f"limit={cg['limit']};ok={cg['ok']}")]
    if sp.get("skipped"):
        rows.append(("report_speedup", 0.0, "skipped (smoke)"))
    else:
        rows.append((
            "report_speedup", 0.0,
            f"batched={sp['batched_points_per_sec']}pts/s;"
            f"scalar={sp['scalar_points_per_sec']}pts/s;"
            f"speedup={sp['speedup']}x;ok={sp['ok']}"))
    return rows


def main() -> None:
    use_compile_cache(ROOT)
    rows: list[tuple] = []
    rows += pt.section_v_worked_example()
    rows += pt.tables_i_ii_nvme_models()
    rows += pt.tables_iii_iv_hdd_models()
    rows += pt.fig3_miss_rate_vs_cache_size()
    rows += pt.tables_v_vi_online_learning()
    rows += pt.tables_vii_ix_strong_scaling()
    rows += pt.fig10_read_throughput()
    rows += _report_summary()
    rows += _dryrun_summary()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")
    artifact = [
        {"name": name, "us_per_call": us, "derived": derived}
        for name, us, derived in rows
    ]
    with open(ARTIFACT, "w") as f:
        json.dump(artifact, f, indent=1)
    print(f"artifact: {ARTIFACT}")


if __name__ == "__main__":
    main()
