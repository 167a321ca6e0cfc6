"""Batched report pipeline (ISSUE 9): ``batched_reports`` /
``sweep(report=...)`` equivalence, the per-stage profile, the duration
guard, and the μ(Q) spec plumbing.

The scalar solver path must stay *bit-identical* to the pre-batching
``report_from_counters`` (that is the mu_load-off guarantee at the report
level), the batched path must agree to solver precision with identical
onset/metastability verdicts, and ``sweep`` must accept an explicit list
of override dicts (the capacity planner's entry point).
"""
import json

import numpy as np
import pytest

from repro.core.traffic import TrafficSpec
from repro.sim import (
    FaultSpec,
    RateSpec,
    RetryPolicy,
    SimSpec,
    batched_reports,
    device_degrade,
    report_from_counters,
    shard_down,
    simulate,
    sweep,
    tier1_counters,
)


def _spec(lam=60.0, mu2=40.0, faulted=True, n_windows=10, **kw):
    faults = None
    if faulted:
        faults = FaultSpec(
            events=(shard_down(1, 0.1, 0.3),
                    device_degrade(2, 0.5, 0.15, 0.4)),
            retry=RetryPolicy(timeout=0.05, max_retries=2,
                              backoff_init=0.3),
        )
    return SimSpec(
        traffic=TrafficSpec(kind="poisson", n_requests=1500, n_pages=256,
                            rate=240.0, seed=5),
        n_shards=4, lam=lam,
        rates=RateSpec(mu1=400.0, mu2=mu2),
        n_windows=n_windows, window_dt=0.05,
        faults=faults, **kw,
    )


def _report_json(rep) -> str:
    def jsonify(o):
        if isinstance(o, np.ndarray):
            return o.tolist()
        if isinstance(o, np.generic):
            return o.item()
        raise TypeError(type(o))
    return json.dumps(rep.to_dict(), sort_keys=True, default=jsonify)


def _assert_reports_close(a, b, tol=1e-10):
    for name in ("q1", "q2", "w1", "w2", "response", "rho1", "rho2"):
        xa = np.asarray(getattr(a.transient, name), float)
        xb = np.asarray(getattr(b.transient, name), float)
        fa, fb = np.isfinite(xa), np.isfinite(xb)
        np.testing.assert_array_equal(fa, fb, err_msg=name)
        if fa.any():
            np.testing.assert_allclose(xa[fa], xb[fb], rtol=0, atol=tol,
                                       err_msg=name)
    assert a.saturation_onset == b.saturation_onset
    assert a.metastable_onset == b.metastable_onset
    for sa, sb in zip(a.shards, b.shards):
        assert sa.saturation_onset == sb.saturation_onset
        assert sa.metastable_onset == sb.metastable_onset
    assert a.response_s == pytest.approx(b.response_s, abs=tol)


def test_scalar_solver_bit_identical_to_reference():
    """batched_reports(solver='scalar') is the pre-batching per-point path,
    byte for byte — the refactor must not move the default output."""
    specs = [_spec(lam=l, faulted=f)
             for l in (40.0, 80.0) for f in (False, True)]
    items = [(s, tier1_counters(s), None) for s in specs]
    ref = [report_from_counters(s, c, t) for s, c, t in items]
    got = batched_reports(items, solver="scalar")
    for a, b in zip(ref, got):
        assert _report_json(a) == _report_json(b)


def test_batched_matches_scalar_reports():
    specs = [_spec(lam=l, mu2=m, faulted=f)
             for l in (40.0, 90.0) for m in (30.0, 55.0)
             for f in (False, True)]
    items = [(s, tier1_counters(s), None) for s in specs]
    scalar = batched_reports(items, solver="scalar")
    batched = batched_reports(items, solver="batched")
    for a, b in zip(scalar, batched):
        _assert_reports_close(a, b)


def test_batched_reports_validation_and_piecewise_fallback():
    with pytest.raises(ValueError, match="solver"):
        batched_reports([], solver="nope")
    # Piecewise-mode points ride the scalar path inside solver='batched'.
    spec = _spec(faulted=False, transient_mode="piecewise")
    items = [(spec, tier1_counters(spec))]
    a = batched_reports(items, solver="batched")[0]
    b = report_from_counters(*items[0])
    assert _report_json(a) == _report_json(b)


def test_duration_guard_on_timed_specs():
    """A timed spec whose window_dt degenerates to 0/NaN (validation
    bypassed — stale pickles, object.__setattr__) fails loudly in the
    report, not with rates divided by zero."""
    spec = _spec(faulted=False)
    ctr = tier1_counters(spec)
    for bad in (0.0, float("nan")):
        broken = object.__new__(SimSpec)
        object.__setattr__(broken, "__dict__", dict(spec.__dict__))
        object.__setattr__(broken, "window_dt", bad)
        with pytest.raises(ValueError, match="window duration"):
            report_from_counters(broken, ctr)


def test_simspec_rejects_nonfinite_window_dt():
    for bad in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="window_dt"):
            _spec(faulted=False, n_windows=4).replace(window_dt=bad)


def test_mu_load_requires_fluid_mode():
    rates = RateSpec(mu1=400.0, mu2=40.0, mu_load=((0.01, 0.1), (0.0, 0.2)))
    spec = _spec(faulted=False).replace(rates=rates)
    assert spec.transient_mode == "fluid"  # accepted
    with pytest.raises(ValueError, match="mu_load"):
        spec.replace(transient_mode="piecewise")


def test_mu_load_rides_report_and_batches_separately():
    """A μ(Q)-enabled spec solves end to end on both report paths (they
    agree), lands in its own batch group, and bends the transient vs the
    fixed-rate solve."""
    base = _spec(faulted=False)
    slow = base.replace(
        rates=RateSpec(mu1=400.0, mu2=40.0,
                       mu_load=((0.0, 0.5), (0.0, 0.5))))
    ctr = tier1_counters(base)  # same traffic: counters shared
    items = [(base, ctr), (slow, ctr)]
    scalar = batched_reports(items, solver="scalar")
    batched = batched_reports(items, solver="batched")
    for a, b in zip(scalar, batched):
        _assert_reports_close(a, b)
    q_base = np.asarray(batched[0].transient.q1)
    q_slow = np.asarray(batched[1].transient.q1)
    assert q_slow.max() > q_base.max()


def test_sweep_report_modes_and_profile():
    base = _spec()
    axes = {"lam": [40.0, 70.0], "rates.mu2": [30.0, 50.0]}
    rb = sweep(base, axes, report="batched", profile=True)
    rs = sweep(base, axes, report="scalar")
    for a, b in zip(rs.reports, rb.reports):
        _assert_reports_close(a, b)
    assert rs.profile is None
    prof = rb.profile
    stages = ("stream_gen", "traffic_gen", "route_stream", "route_mrc",
              "engine_dispatch_submit", "engine_dispatch_wait",
              "report_solve", "assembly")
    assert set(prof) >= set(stages) | {"total", "n_points"}
    assert "engine_dispatch" not in prof
    assert prof["n_points"] == 4
    assert all(prof[k] >= 0 for k in stages)
    payload = json.loads(rb.to_json())
    assert payload["profile"]["report_solver"] == "batched"
    with pytest.raises(ValueError, match="report"):
        sweep(base, axes, report="nope")


def test_sweep_accepts_explicit_point_list():
    base = _spec(faulted=False)
    pts = [{"lam": 45.0}, {"lam": 85.0, "rates.mu2": 30.0}]
    res = sweep(base, pts, report="batched")
    assert res.points == (pts[0], pts[1])
    assert res.axes == {}
    direct = simulate(base.replace(**pts[1]))
    assert res.reports[1].misses == direct.misses
    assert res.reports[1].lam_eff == pytest.approx(direct.lam_eff)
