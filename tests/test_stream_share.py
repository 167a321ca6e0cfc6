"""Stream sharing in the megabatch sweep: one sweep call makes each distinct
stream (one :meth:`SimSpec.stream_signature`) once and serves every point
of that signature from it, with counters and reports bit-equal to the
unbatched reference path."""
import importlib
import json

import numpy as np
import pytest

from repro.core.traffic import TrafficSpec
from repro.sim import (
    FaultSpec,
    RateSpec,
    SimSpec,
    device_degrade,
    shard_down,
    sweep,
)
from repro.storage.tiered_store import StoreConfig

# The module, not the sweep() that repro.sim re-exports under its name.
sweep_mod = importlib.import_module("repro.sim.sweep")

# A faulted wall-clock spec: shard 1 goes down for two of six windows.
TIMED = SimSpec(
    traffic=TrafficSpec(kind="irm", n_requests=600, n_pages=128,
                        zipf_s=0.8, seed=7, rate=100.0),
    store=StoreConfig(n_lines=16, policy="lru"),
    n_shards=4,
    lam=25.0,
    rates=RateSpec(mu1=100.0, mu2=33.0),
    p12_override=0.2,
    window_dt=1.0,
    transient_mode="fluid",
    faults=FaultSpec(events=(shard_down(1, 2.0, 4.0),), refill_cold=True),
)
UNTIMED = SimSpec(
    traffic=TrafficSpec(kind="poisson", n_requests=300, n_pages=96,
                        write_fraction=0.25, seed=5),
    store=StoreConfig(n_lines=16, policy="lru"),
    n_shards=3,
    lam=20.0,
    n_windows=4,
    rates=RateSpec(source="paper"),
)
# Traffic with no rate of its own: the arrival rate is lam * n_shards. A
# fixed window count keeps the window grid the same for every lam.
NO_RATE = TIMED.replace(**{"traffic.rate": 0.0, "faults": None,
                           "n_windows": 6})

# (case, base, axes, mrc, streams the grid needs)
GRIDS = [
    # (a) a faulted timed grid over store knobs alone: one stream.
    ("faulted_knobs", TIMED,
     {"store.alpha": [0.3, 0.7], "store.policy": ["lru", "lfu", "ws"]},
     "auto", 1),
    # (b) one stream per traffic seed.
    ("seed_axis", UNTIMED,
     {"traffic.seed": [1, 2, 3], "store.alpha": [0.3, 0.7]}, "auto", 3),
    # (c) agg_rate is in the key: one stream per lam.
    ("lam_axis_no_rate", NO_RATE,
     {"lam": [20.0, 40.0], "store.alpha": [0.3, 0.7]}, "auto", 2),
    # (d) sizes in different batch groups still share one stream.
    ("sizes_across_groups", UNTIMED,
     {"store.n_lines": [8, 16, 32]}, "off", 1),
    # A shard_down reroutes arrivals, so it is a stream of its own; a
    # degrade acts on the queuing side and shares the unfaulted stream.
    ("fault_axis", TIMED,
     {"faults": [None,
                 FaultSpec(events=(device_degrade(1, 0.5, 1.0, 3.0),)),
                 FaultSpec(events=(shard_down(1, 2.0, 4.0),)),
                 FaultSpec(events=(shard_down(2, 1.0, 3.0),))],
      "store.policy": ["lru", "lfu"]}, "auto", 3),
]


def _spy(monkeypatch):
    """Record every generator call the sweep makes, and every point's
    Tier1Counters on their way to the report stage."""
    made, counters = [], []

    def wrap(fn):
        def recorder(traffic, **kw):
            made.append((traffic, kw.get("default_rate")))
            return fn(traffic, **kw)
        return recorder

    def reports(items, **kw):
        counters.append([c for _, c, _ in items])
        return real_reports(items, **kw)

    real_reports = sweep_mod.batched_reports
    monkeypatch.setattr(sweep_mod, "make_stream",
                        wrap(sweep_mod.make_stream))
    monkeypatch.setattr(sweep_mod, "make_timed_stream",
                        wrap(sweep_mod.make_timed_stream))
    monkeypatch.setattr(sweep_mod, "batched_reports", reports)
    return made, counters


@pytest.mark.parametrize("base,axes,mrc,n_streams",
                         [g[1:] for g in GRIDS], ids=[g[0] for g in GRIDS])
def test_sweep_makes_each_stream_once(monkeypatch, base, axes, mrc,
                                      n_streams):
    made, counters = _spy(monkeypatch)
    a = sweep(base, axes, mrc=mrc, report="batched", profile=True)
    assert len(made) == n_streams
    specs = [base.replace(**pt) for pt in a.points]
    n_sigs = len({s.cache_signature() for s in specs})
    assert len({s.stream_signature() for s in specs}) == n_streams
    assert a.profile["stream_shared"] == n_sigs - n_streams

    b = sweep(base, axes, batch=False, report="batched")
    got, want = counters
    assert len(got) == len(want) == len(a.points)
    for ca, cb in zip(got, want):
        for f in ca._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ca, f)), np.asarray(getattr(cb, f)),
                err_msg=f"Tier1Counters.{f} differs")
    assert _reports_json(a) == _reports_json(b)


def _reports_json(res):
    return json.dumps([rep.to_dict() for rep in res.reports],
                      default=sweep_mod._jsonify)


def test_knob_grid_makes_one_timed_stream(monkeypatch):
    """The faulted knob grid makes its one stream through the wall-clock
    generator, and every point but the first is served from it."""
    made, _ = _spy(monkeypatch)
    axes = {"store.alpha": [0.3, 0.7], "store.policy": ["lru", "lfu", "ws"]}
    res = sweep(TIMED, axes, profile=True)
    assert made == [(TIMED.traffic, TIMED.agg_rate())]
    assert res.profile["stream_shared"] == len(res.points) - 1 == 5


def test_shared_stream_is_read_only():
    """A stream shared among points rejects in-place writes."""
    n_windows, _ = TIMED.window_grid()
    st = sweep_mod._stream(TIMED, TIMED.n_shards, n_windows, True, None)
    for arr in st:
        assert not arr.flags.writeable
        with pytest.raises(ValueError):
            arr[...] = 0


@pytest.mark.parametrize("spec", [TIMED, UNTIMED, NO_RATE],
                         ids=["timed_faulted", "untimed", "timed_no_rate"])
def test_stream_signature_is_cache_signature_without_store(spec):
    sig = spec.cache_signature()
    assert sig[1] == spec.store
    assert spec.stream_signature() == sig[:1] + sig[2:]
    knobs = spec.replace(**{"store.alpha": 0.9, "store.beta": 0.1,
                            "store.threshold": 0.3, "store.policy": "lfu",
                            "store.n_lines": 64})
    assert knobs.cache_signature() != sig
    assert knobs.stream_signature() == spec.stream_signature()
    assert (spec.replace(**{"traffic.seed": 99}).stream_signature()
            != spec.stream_signature())
