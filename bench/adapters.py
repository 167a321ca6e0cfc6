"""A configuration file as the program's ``SimSpec``: the one place the
benchmark builds the system under test's input from its own data; and the
draws a curve's and a sweep's queries and checks make from a run's
seed."""
from __future__ import annotations

from traffic_gen import make_trace, rng_for


# The program's name of each traffic kind the benchmark generates.
PROGRAM_KIND = {"irm": "irm", "poisson_decay": "poisson"}


def sim_spec(cfg: dict, traffic_seed: int = 0):
    """The configuration as a ``SimSpec``; ``traffic_seed`` is the seed of
    the stream the program makes itself (kinds it is not handed a trace
    for)."""
    from repro.core.traffic import TrafficSpec
    from repro.sim import SimSpec
    from repro.sim.spec import StoreConfig
    st, store, win = cfg["stream"], cfg["store"], cfg["windows"]
    n_windows = int(win["n_windows"])
    window_dt = (float(win["window_dt"]) if "window_dt" in win else
                 int(st["n_requests"]) / float(st["rate"]) / n_windows)
    traffic = dict(kind=PROGRAM_KIND[st["kind"]],
                   n_requests=int(st["n_requests"]),
                   n_pages=int(st["n_pages"]),
                   write_fraction=float(st["write_fraction"]),
                   rate=float(st["rate"]), seed=int(traffic_seed))
    if st["kind"] == "poisson_decay":
        traffic.update(decay_tau=float(st["decay_tau"]),
                       arrival_rate=float(st["arrival_rate"]))
    return SimSpec(
        traffic=TrafficSpec(**traffic),
        store=StoreConfig(n_lines=int(store["n_lines"]),
                          policy=store["policy"],
                          prefetch=bool(store["prefetch"])),
        n_shards=int(store["n_shards"]),
        mapping=store["mapping"],
        n_windows=n_windows,
        window_dt=window_dt,
        faults=fault_spec(cfg.get("faults")),
    )


def fault_spec(faults):
    """A configuration's ``faults`` block as the program's ``FaultSpec``."""
    if not faults:
        return None
    from repro.sim import (FaultSpec, RetryPolicy, device_degrade,
                           shard_down)
    events = tuple(shard_down(int(d["shard"]), float(d["t0"]), float(d["t1"]))
                   for d in faults.get("shard_down", ()))
    events += tuple(device_degrade(int(d["tier"]), float(d["factor"]),
                                   float(d["t0"]), float(d["t1"]))
                    for d in faults.get("device_degrade", ()))
    retry = faults.get("retry")
    return FaultSpec(events=events,
                     retry=RetryPolicy(**retry) if retry else None,
                     refill_cold=bool(faults.get("refill_cold", True)))


def fault_reference_args(cfg: dict, policy: str) -> dict:
    """The keyword arguments :func:`reference.fault_counters` takes for a
    grid point of ``cfg`` that runs ``policy``."""
    st, store, win = cfg["stream"], cfg["store"], cfg["windows"]
    faults = cfg.get("faults") or {}
    return dict(n_shards=int(store["n_shards"]), mapping=store["mapping"],
                n_pages=int(st["n_pages"]), n_lines=int(store["n_lines"]),
                policy=policy, n_windows=int(win["n_windows"]),
                window_dt=float(win["window_dt"]),
                down=[(int(d["shard"]), float(d["t0"]), float(d["t1"]))
                      for d in faults.get("shard_down", ())],
                refill_cold=bool(faults.get("refill_cold", True)))


def reference_args(cfg: dict, **over) -> dict:
    """The keyword arguments :func:`reference.counters` takes for ``cfg``."""
    st, store = cfg["stream"], cfg["store"]
    n_windows = int(cfg["windows"]["n_windows"])
    if store["policy"] != "lru" or store["prefetch"]:
        raise ValueError("the reference covers LRU without prefetch")
    args = dict(n_shards=int(store["n_shards"]), mapping=store["mapping"],
                n_lines=int(store["n_lines"]), n_windows=n_windows,
                window_dt=int(st["n_requests"]) / float(st["rate"])
                / n_windows)
    args.update(over)
    return args


def curve_sizes(mix: dict) -> list[int]:
    """The cache sizes (lines per shard) a curve mix asks for."""
    s = mix["sizes"]
    return [int(s["first"]) + int(s["step"]) * k
            for k in range(int(s["count"]))]


def curve_trace(cfg: dict, mix: dict):
    """A curve mix's base trace: made from the mix's fixed ``stream_seed``,
    the same in every run, so every run does the same work."""
    return make_trace(cfg["stream"], int(mix["stream_seed"]))


def curve_offset(seed: int, query: int, total: int) -> int:
    """How far query ``query`` of a run rotates the curve's trace."""
    return int(rng_for(seed, 3, query).integers(total))


def curve_checks(seed: int, n_queries: int, sizes, n_shards: int) -> list:
    """The ``(query, size, shard)`` answers a curve run compares with the
    reference: every size once, its query and shard drawn from the seed."""
    rng = rng_for(seed, 4)
    return [(int(rng.integers(n_queries)), int(size),
             int(rng.integers(n_shards))) for size in sizes]


def sweep_traffic_seed(seed: int, query: int) -> int:
    """The traffic seed of a sweep run's query ``query`` (set-up's grid is
    query ``-1``)."""
    return int(rng_for(seed, 5, query + 1).integers(2**62))
