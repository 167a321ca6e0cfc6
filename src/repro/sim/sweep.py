"""Sweep engine: evaluate a grid of scenarios with shared work batched.

``sweep(base, axes)`` expands a cartesian grid of dotted-path overrides
over a base :class:`SimSpec` (e.g. ``{"store.n_lines": [16, 64, 256],
"n_shards": [2, 4], "store.policy": ["ws", "lru"]}``) and returns one
:class:`SimReport` per point.

Four levels of work sharing make wide sweeps cheap:

1. **Cache-run dedup** — points that differ only in queuing-side knobs
   (λ, k, flow, rates, p12_override) share a
   :meth:`SimSpec.cache_signature`; the expensive tier-1 counter
   simulation runs once per signature. One level up, signatures that
   differ only in the store share a :meth:`SimSpec.stream_signature`,
   and the host stream preparation (generation, failover, binning,
   partition) runs once per stream signature per call.
2. **Megabatch vmap** — signatures whose *structural* engine is identical
   (same ``StoreConfig.static_config()``, shard count, mapping) stack into
   one ``[point, shard, len]`` batch processed by a single triply-batched
   ``run_stream`` call. The scalar learning knobs (``alpha``, ``beta``,
   ``threshold`` and the policy selector) ride along as **traced**
   :class:`~repro.storage.tiered_store.StoreHyper` operands on the point
   axis, so a whole hyperparameter/policy grid compiles the engine **once**
   instead of once per combination.
3. **Bucketed padding** — each point is padded to the next power-of-two
   length bucket of *its own* max shard load (floor :data:`MIN_BUCKET`)
   rather than the group-wide max, so short streams stop paying for the
   longest one; buckets dispatch as separate stacked calls.
4. **Device sharding + async dispatch** — the point axis of every stacked
   call is sharded across all local devices (``shard_map`` via the
   :mod:`repro.launch.compat` shims) and calls are dispatched
   asynchronously: host-side traffic generation, padding and queuing
   solves for later groups overlap device compute for earlier ones.

Windowed telemetry (``SimSpec.n_windows``) rides the same batch: window
ids are a data operand next to the stream (pads carry the dropped
out-of-range id), so the ``[point, shard, n_windows]`` counters add no
compiles beyond the structural split on ``n_windows`` itself. Wall-clock
windows (``SimSpec.window_dt``) ride the *same* operand: arrival times
are binned host-side in float64 (:func:`timestamp_window_ids`) and the
resulting int32 ids stack next to the stream, so timestamped grids share
one compiled engine with request-index grids of the same window count —
and long-horizon traces bin exactly (no f32 drift in the scan).

Compiles of the batched engine are observable via
:func:`engine_compile_count` (a trace-time counter used by
``benchmarks/bench_sweep.py`` to gate compile-cache behavior).

**Miss-rate-curve routing** (``mrc=`` keyword): ``store.n_lines`` is
*structural* — every cache size costs a fresh engine compile and a fresh
pass over the stream. When a grid axis varies only the cache size and the
spec sits inside the exact stack-distance domain (LRU, no prefetch — see
:func:`repro.sim.mrc.mrc_unsupported_reason`), the whole size axis is
served by :func:`repro.sim.mrc.mrc_tier1_counters` instead: one distance
pass, zero engine compiles, counters bit-identical to the tier-1 engine.
``mrc="auto"`` (default) routes eligible multi-size groups and falls back
to the engine with a logged reason otherwise; ``"off"`` disables the
path; ``"require"`` raises ``ValueError`` if any group cannot be routed
(the compile-budget guard for capacity-planning sweeps).

**Streaming routing** (``stream=`` keyword): the megabatch stacks whole
traces on device, so a grid point with a multi-million-request stream
(or a ``tenant_mix`` workload, whose per-tenant attribution only the
streaming path produces) is better served by the chunked replay engine
(:mod:`repro.sim.stream`): bounded device memory, at most two compiles,
counters bit-identical to the megabatch. ``stream="auto"`` (default) routes
``tenant_mix`` signatures and streams longer than
:data:`STREAM_THRESHOLD` requests; ``"off"`` forces everything through
the megabatch.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import warnings
from typing import Callable, Mapping, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.core.queuing import fluid_compile_count, reset_fluid_compile_count
from repro.core.traffic import make_stream, make_timed_stream
from repro.launch.compat import device_mesh, shard_map
from repro.sim.engine import (
    SimReport,
    TenantCounters,
    Tier1Counters,
    batched_reports,
    counters_from_stats,
    fault_owner,
    report_from_counters,
    sim_n_pages,
    tier1_counters,
)
from repro.sim.mrc import mrc_tier1_counters, mrc_unsupported_reason
from repro.sim.spans import count, span
from repro.sim.stream import stream_tier1_counters
from repro.sim.spec import SimSpec
from repro.storage.tiered_store import (
    StoreConfig,
    StoreHyper,
    partition_streams,
    run_stream,
    timestamp_window_ids,
)

__all__ = [
    "expand_grid",
    "sweep",
    "SweepResult",
    "engine_compile_count",
    "reset_engine_compile_count",
    "fluid_compile_count",
    "reset_fluid_compile_count",
]

log = logging.getLogger(__name__)

# Smallest padded stream-length bucket; lengths round up to powers of two so
# ragged groups land in a handful of shapes instead of one shape per point.
MIN_BUCKET = 16
# Streams longer than this route through the chunked replay engine under
# stream="auto": stacking them whole on device stops paying off before the
# megabatch's compile sharing does.
STREAM_THRESHOLD = 1 << 20
# Default lax.scan unroll for the batched engine (semantics-preserving).
DEFAULT_UNROLL = 4

# The batched engine is cached per (static store, unroll, n_devices); the
# counter increments at trace time, i.e. exactly once per XLA compile.
_ENGINE_CACHE: dict[tuple, Callable] = {}
_ENGINE_COMPILES = [0]


def engine_compile_count() -> int:
    """Number of XLA compiles of the batched sweep engine so far."""
    return _ENGINE_COMPILES[0]


def reset_engine_compile_count() -> None:
    _ENGINE_COMPILES[0] = 0


def expand_grid(axes: Mapping[str, Sequence]) -> list[dict]:
    """Cartesian product of ``{dotted.path: values}`` into override dicts."""
    if not axes:
        return [{}]
    keys = list(axes)
    return [
        dict(zip(keys, combo))
        for combo in itertools.product(*(axes[k] for k in keys))
    ]


@dataclasses.dataclass(frozen=True)
class SweepResult:
    base: SimSpec
    axes: dict
    points: tuple          # override dict per point
    reports: tuple         # SimReport per point
    # sweep(profile=True): per-stage wall-clock seconds, one key per
    # repro.sim.spans span — stream_gen (host-side traffic generation +
    # partitioning for the megabatch, once per stream signature;
    # traffic_gen is its generation alone, and the counter stream_shared
    # the signatures that reused a stream), engine_dispatch_submit /
    # engine_dispatch_wait (the megabatch's device engine calls and their
    # gather), route_stream / route_mrc (the routed chunked-replay and MRC
    # paths, with their own stream_chunk_* / mrc_* spans), unbatched
    # (batch=False's per-point engine runs), report_solve (queuing-network
    # solves), assembly (SimReport construction) and total.
    profile: Optional[dict] = None

    def rows(self) -> list[dict]:
        """One flat dict per point: the overrides + aggregate metrics."""
        out = []
        for pt, rep in zip(self.points, self.reports):
            d = rep.to_dict()
            d.pop("shards")
            d.pop("spec")
            out.append({**{str(k): v for k, v in pt.items()}, **d})
        return out

    def to_json(self, path: Optional[str] = None) -> str:
        payload = {
            "axes": {k: list(v) for k, v in self.axes.items()},
            "n_points": len(self.points),
            "points": [
                {**{str(k): v for k, v in pt.items()}, **rep.to_dict()}
                for pt, rep in zip(self.points, self.reports)
            ],
        }
        if self.profile is not None:
            payload["profile"] = dict(self.profile)
        text = json.dumps(payload, indent=2, default=_jsonify)
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text


def _jsonify(obj):
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.generic):  # any numpy scalar, incl. np.bool_
        return obj.item()
    raise TypeError(f"not JSON serializable: {type(obj)!r}")


def _batch_key(spec: SimSpec) -> tuple:
    """Signatures with equal batch keys share one compiled engine: only the
    *structural* store config splits groups — the scalar learning knobs
    (alpha/beta/threshold/policy) are traced operands and stack instead.
    The window count shapes the accumulator arrays, so it is structural
    too — but window ids are data (wall-clock specs bin their arrival
    times host-side into the same int32 operand), so one compile serves
    any window layout, timed or not."""
    n_windows, _ = spec.window_grid()
    return (spec.store.static_config(), spec.n_shards, spec.mapping,
            n_windows)


def _mrc_group_key(spec: SimSpec) -> tuple:
    """Signatures equal after erasing ``store.n_lines`` form one MRC group:
    they share the stream, partition, faults and window layout and differ
    only in cache size — exactly the axis one stack-distance pass covers."""
    return spec.replace(**{"store.n_lines": 1}).cache_signature()


def _route_mrc(
    unique: Mapping[tuple, SimSpec], mrc: str,
    profile: Optional[dict] = None,
) -> dict[tuple, Tier1Counters]:
    """Serve every eligible size-only signature group via the one-pass MRC
    engine. Returns ``{signature: counters}`` for the routed signatures
    (bit-identical to the tier-1 engine); the caller runs the rest through
    the batched engine. ``mrc="require"`` raises if any group is
    ineligible; ``"auto"`` routes only groups with >= 2 sizes (a single
    size gains nothing over the engine). ``profile`` collects the MRC
    pass's ``mrc_*`` spans (:func:`repro.sim.mrc.mrc_tier1_counters`)."""
    groups: dict[tuple, list[tuple]] = {}
    for sig, spec in unique.items():
        groups.setdefault(_mrc_group_key(spec), []).append(sig)

    counters: dict[tuple, Tier1Counters] = {}
    for sigs in groups.values():
        rep = unique[sigs[0]]
        reason = mrc_unsupported_reason(rep)
        if reason is not None:
            if mrc == "require":
                raise ValueError(
                    "mrc='require' but the MRC path cannot serve this "
                    f"grid: {reason}"
                )
            if len(sigs) >= 2:
                log.info(
                    "sweep: MRC fallback to the tier-1 engine for %d sizes (%s)",
                    len(sigs), reason,
                )
            continue
        if len(sigs) < 2 and mrc != "require":
            continue
        sizes = sorted(unique[s].store.n_lines for s in sigs)
        log.info(
            "sweep: MRC route — %d cache sizes from one distance pass "
            "(policy=lru, n_shards=%d)",
            len(sizes), rep.n_shards,
        )
        by_size = mrc_tier1_counters(rep, sizes, profile=profile)
        for s in sigs:
            counters[s] = by_size[int(unique[s].store.n_lines)]
    return counters


def _route_stream(
    unique: Mapping[tuple, SimSpec], stream: str, *,
    profile: Optional[dict] = None,
) -> tuple[dict[tuple, Tier1Counters], dict[tuple, TenantCounters]]:
    """Serve ``tenant_mix`` and oversized-stream signatures via the chunked
    replay engine (:mod:`repro.sim.stream`): bounded device memory, at most
    two compiles, counters bit-identical to the megabatch. Returns
    ``({signature: counters}, {signature: tenant_counters})`` for the
    routed signatures; the caller runs the rest through the megabatch.
    ``profile`` threads per-chunk sub-timings through to
    :func:`repro.sim.stream.stream_tier1_counters`."""
    counters: dict[tuple, Tier1Counters] = {}
    tenants: dict[tuple, TenantCounters] = {}
    if stream == "off":
        return counters, tenants
    for sig, spec in unique.items():
        mix = spec.traffic.kind == "tenant_mix"
        if not (mix or spec.traffic.n_requests > STREAM_THRESHOLD):
            continue
        log.info(
            "sweep: stream route — %s, %d requests (chunked replay)",
            "tenant_mix" if mix else "oversized stream",
            spec.traffic.n_requests,
        )
        ctr, tc, _ = stream_tier1_counters(spec, profile=profile)
        counters[sig] = ctr
        if tc is not None:
            tenants[sig] = tc
    return counters, tenants


def _bucket_cap(n: int) -> int:
    """Next power-of-two length bucket (floor MIN_BUCKET) for a shard load."""
    cap = MIN_BUCKET
    while cap < n:
        cap <<= 1
    return cap


def _stack_hypers(stores: Sequence[StoreConfig]) -> StoreHyper:
    """Concrete [N]-leaf StoreHyper stack for a list of store configs."""
    hypers = [s.hyper() for s in stores]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *hypers)


def _batched_engine(
    store: StoreConfig, unroll: int, devices: tuple, n_windows: int,
    donate: bool = True,
) -> Callable:
    """The one-compile megabatch engine for a structural store config:
    ``(hyper [N], pages [N, S, L], writes [N, S, L], win [N, S, L]) ->
    StreamStats [N, S]`` (windowed counters ``[N, S, n_windows]``), point
    axis sharded over ``devices``. Each row runs
    :func:`repro.storage.tiered_store.run_stream`. Wall-clock specs feed
    the same ``win`` operand (arrival times become int32 ids host-side), so
    timed and request-index grids share this one engine. Cached so repeated
    sweeps reuse both the wrapper and jit's compile cache.

    ``donate=True`` donates the three stacked chunk buffers to the dispatch
    (``donate_argnums``) so XLA may recycle their allocations while the
    engine runs — ``donate=False`` keeps the undonated baseline
    available (buffers stay valid after the call)."""
    key = (store, unroll, devices, n_windows, donate)
    fn = _ENGINE_CACHE.get(key)
    if fn is not None:
        return fn

    # Named for the profiler: the trace shows jit_megabatch_engine.
    def megabatch_engine(hyper, sh_pages, sh_writes, sh_win):
        _ENGINE_COMPILES[0] += 1  # trace-time: once per XLA compile

        def point(h, p, w, wi):
            return jax.vmap(
                lambda pp, ww, wwi: run_stream(
                    store, pp, ww, hyper=h, unroll=unroll,
                    n_windows=n_windows, window_ids=wwi,
                )
            )(p, w, wi)

        return jax.vmap(point)(hyper, sh_pages, sh_writes, sh_win)
    n_in = 4

    if len(devices) > 1:
        spec = PartitionSpec("points")
        jfn = jax.jit(shard_map(
            megabatch_engine,
            mesh=device_mesh("points", devices),
            in_specs=(spec,) * n_in,
            out_specs=spec,
            check_vma=True,
        ), donate_argnums=(1, 2, 3) if donate else ())
    else:
        jfn = jax.jit(megabatch_engine,
                      donate_argnums=(1, 2, 3) if donate else ())

    if donate:
        # The stacked stream operands have no same-shape output to alias
        # (the StreamStats counters are tiny), so XLA can only free them
        # early, not reuse them — intended; silence just that warning.
        def fn(*args):
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return jfn(*args)
    else:
        fn = jfn
    _ENGINE_CACHE[key] = fn
    return fn


class _Stream(NamedTuple):
    """One stream signature's host preparation, shared read-only by every
    member with that signature."""

    sh_pages: np.ndarray  # [S, own_cap] partitioned stream
    sh_writes: np.ndarray
    sh_win: np.ndarray   # [S, own_cap] window ids (n_windows = pad/drop);
                         # timed specs pre-bin arrival times into these
    counts: np.ndarray   # per-shard real request counts
    shard_writes: np.ndarray  # per-shard write counts


class _Member(NamedTuple):
    """One unique cache signature prepared for stacking."""

    bucket: int          # power-of-two padded length for this point
    sig: tuple           # cache signature
    spec: SimSpec
    stream: _Stream


@dataclasses.dataclass
class _PendingBucket:
    """One dispatched stacked engine call awaiting materialization."""

    sigs: list           # cache signature per real point
    counts: list         # per-point per-shard real request counts
    writes: list         # per-point per-shard write counts
    cap: int             # padded stream length (bucket)
    stats: object        # StreamStats of device arrays (async futures)

    def gather(self) -> dict:
        # Blocks on the device.
        stacked = jax.tree.map(np.asarray, self.stats)
        out = {}
        for i, sig in enumerate(self.sigs):
            stats_i = jax.tree.map(lambda a: a[i], stacked)
            out[sig] = counters_from_stats(
                stats_i, self.counts[i], self.writes[i], cap=self.cap
            )
        return out


def _stream(spec: SimSpec, n_shards: int, n_windows: int, timed: bool,
            prof: Optional[dict]) -> _Stream:
    """A stream made, failed over, binned and partitioned for stacking; its
    traffic generation alone is the ``traffic_gen`` span. The arrays are
    read-only: every member of the stream signature shares them."""
    n_windows_i, window_dt = spec.window_grid()
    assert n_windows_i == n_windows  # grouped by batch key
    if timed:
        with span("traffic_gen", prof):
            pages, is_write, times = make_timed_stream(
                spec.traffic, default_rate=spec.agg_rate())
        n_pages_i = sim_n_pages(spec, pages)
        # Fault schedules ride the megabatch as *data*: the failover
        # remap happens host-side and only reshuffles the owner
        # operand, so a fault grid shares one compiled engine.
        own = fault_owner(spec, pages, times, n_pages_i)
        # Bin arrival times host-side (float64) into the same int32
        # window-id operand the index path uses — one engine, exact
        # long-horizon binning.
        gwin = timestamp_window_ids(times, n_windows, window_dt)
        sh_p, sh_w, counts, owner, sh_tw = partition_streams(
            pages, is_write, n_shards=n_shards, mapping=spec.mapping,
            n_pages=n_pages_i, n_windows=n_windows, window_ids=gwin,
            owner=own,
        )
    else:
        with span("traffic_gen", prof):
            pages, is_write = make_stream(spec.traffic)
        sh_p, sh_w, counts, owner, sh_tw = partition_streams(
            pages, is_write, n_shards=n_shards, mapping=spec.mapping,
            n_pages=sim_n_pages(spec, pages), n_windows=n_windows,
        )
    out = _Stream(sh_p, sh_w, sh_tw, counts,
                  np.bincount(owner[is_write], minlength=n_shards))
    for arr in out:
        arr.setflags(write=False)
    return out


def _member(spec: SimSpec, sig: tuple, streams: dict, n_shards: int,
            n_windows: int, timed: bool, prof: Optional[dict]) -> _Member:
    """One signature ready for stacking. Its stream is made once per
    :meth:`SimSpec.stream_signature` per sweep call: ``streams`` holds the
    ones already made, and a member served from it adds one to the
    ``stream_shared`` counter instead of opening ``traffic_gen``."""
    key = spec.stream_signature()
    stream = streams.get(key)
    if stream is None:
        stream = streams[key] = _stream(spec, n_shards, n_windows, timed,
                                        prof)
    else:
        count("stream_shared", prof)
    return _Member(bucket=_bucket_cap(stream.sh_pages.shape[1]), sig=sig,
                   spec=spec, stream=stream)


def _dispatch_group(
    specs: list[SimSpec], sigs: list, streams: dict, *, unroll: int,
    devices: tuple, donate: bool = True, _prof: Optional[dict] = None,
) -> list[_PendingBucket]:
    """Partition, bucket, pad and asynchronously dispatch every unique cache
    signature of one batch-key group. Returns pending buckets; device compute
    proceeds while the caller prepares and dispatches later groups.
    ``streams`` is the sweep call's ``{stream signature: stream}``: a stream
    is made once per stream signature per call, whichever group first needs
    it, and shared by every later member (see :func:`_member`). ``_prof``
    collects the ``stream_gen`` span (with a ``traffic_gen`` span for each
    stream made inside it), the ``stream_shared`` counter and the
    ``engine_dispatch_submit`` span."""
    store_static = specs[0].store.static_config()
    n_shards = specs[0].n_shards
    n_windows, window_dt0 = specs[0].window_grid()
    timed = window_dt0 is not None
    n_dev = len(devices)

    with span("stream_gen", _prof):
        members = [_member(spec, sig, streams, n_shards, n_windows, timed,
                           _prof)
                   for spec, sig in zip(specs, sigs)]

    # Submission side of the engine stage: host→device transfer of the
    # stacked operands and the async calls (device compute is still in
    # flight when this returns). The wait side (device compute + gather
    # transfer) is the caller's ``engine_dispatch_wait`` span.
    with span("engine_dispatch_submit", _prof):
        buckets: dict[int, list[_Member]] = {}
        for m in members:
            buckets.setdefault(m.bucket, []).append(m)

        pending = []
        for cap, group in sorted(buckets.items()):
            n = len(group)
            # The point axis must split over the devices.
            n_pad = -(-n // n_dev) * n_dev
            sh_pages = np.zeros((n_pad, n_shards, cap), np.int32)
            sh_writes = np.zeros((n_pad, n_shards, cap), bool)
            # Bucket-extension positions are padding: window id n_windows
            # drops them from the windowed counters (so windowed telemetry
            # is bit-identical across bucket choices).
            sh_win = np.full((n_pad, n_shards, cap), n_windows, np.int32)
            for i, m in enumerate(group):
                st = m.stream
                w = st.sh_pages.shape[1]
                # Rows come pre-padded with their shard's last page;
                # extending that edge-repeat keeps the padding a pure-hit
                # stream.
                sh_pages[i, :, :w] = st.sh_pages
                sh_pages[i, :, w:] = st.sh_pages[:, -1:]
                sh_writes[i, :, :w] = st.sh_writes
                sh_win[i, :, :w] = st.sh_win
            # Padded points: discarded after the gather.
            sh_pages[n:] = sh_pages[0]
            sh_writes[n:] = sh_writes[0]

            stores = [m.spec.store for m in group]
            stores += [stores[0]] * (n_pad - n)
            hyper = _stack_hypers(stores)

            eng = _batched_engine(store_static, unroll, devices, n_windows,
                                  donate)
            log.info(
                "sweep: dispatch %d points x %d shards @ len %d "
                "(n_lines=%d, windows=%d, timed=%s, devices=%d)",
                n, n_shards, cap, store_static.n_lines, n_windows, timed,
                n_dev,
            )
            operands = (sh_pages, sh_writes, sh_win)
            if n_dev == 1:
                # One device: place the operands there; the engine
                # follows.
                operands = jax.device_put(operands, devices[0])
                hyper = jax.device_put(hyper, devices[0])
            stats = eng(hyper, *operands)
            pending.append(_PendingBucket(
                sigs=[m.sig for m in group],
                counts=[m.stream.counts for m in group],
                writes=[m.stream.shard_writes for m in group],
                cap=cap,
                stats=stats,
            ))
    return pending


def sweep(
    base: SimSpec,
    axes,
    *,
    batch: bool = True,
    unroll: int = DEFAULT_UNROLL,
    mrc: str = "auto",
    stream: str = "auto",
    report: str = "auto",
    donate: bool = True,
    profile: bool = False,
    verbose: bool = False,
    devices: Optional[Sequence] = None,
) -> SweepResult:
    """Evaluate ``base`` at every point of the ``axes`` grid.

    ``axes`` is either a ``{dotted.path: values}`` mapping (expanded to
    its cartesian grid) or an explicit sequence of override dicts — the
    capacity planner's path for sweeping a hand-picked candidate set in
    one batched call.

    ``batch=True`` runs the megabatched one-compile engine (see module
    docstring); ``batch=False`` simulates every signature independently
    (reference path, bit-identical counters). ``unroll`` chunks the
    per-request scan of the batched engine.

    ``mrc`` controls miss-rate-curve routing of cache-size axes (see
    module docstring): ``"auto"`` serves eligible size-only groups from
    one stack-distance pass, ``"off"`` always scans, ``"require"`` raises
    ``ValueError`` when the MRC path cannot serve the grid (incompatible
    with ``batch=False``, whose purpose is the unbatched engine run).

    ``stream`` controls chunked-replay routing (see module docstring):
    ``"auto"`` serves ``tenant_mix`` signatures (adding per-tenant
    attribution to their reports) and streams past
    :data:`STREAM_THRESHOLD` requests via :mod:`repro.sim.stream`;
    ``"off"`` forces the megabatch.

    ``report`` picks the report-stage solver
    (:func:`repro.sim.engine.batched_reports`): ``"batched"`` stacks every
    fluid-mode point's windowed rates into one ``[point, shard, window]``
    jitted solve (one compile per structural config —
    :func:`fluid_compile_count`); ``"scalar"`` solves per point with the
    numpy reference loop — bit-identical ``SimReport`` JSON to the
    pre-batching per-point path; ``"auto"`` follows ``batch``. Batched and
    scalar reports agree to ~1e-13 (analytic k=1 path).

    ``donate=True`` donates the stacked stream buffers to each megabatch
    dispatch (``donate_argnums``); ``donate=False`` keeps the undonated
    baseline.

    ``devices`` are the devices the megabatch's point axis is sharded
    over (default: every local device); one device runs it unsharded
    there. The routed stream/MRC paths and the report stage run on the
    default device.

    ``profile=True`` attaches a per-stage wall-clock breakdown (seconds,
    one key per :func:`repro.sim.spans.span`, each also a ``repro.<key>``
    event in a profiler trace) to :attr:`SweepResult.profile`, serialized
    by ``to_json``: ``stream_gen`` (with ``traffic_gen``, the generation
    alone, inside it; a stream is made once per
    :meth:`SimSpec.stream_signature` per call, and the integer counter
    ``stream_shared`` counts the megabatch signatures served by a stream
    already made), ``engine_dispatch_submit`` (host-side transfer and
    submission of the async megabatch calls), ``engine_dispatch_wait``
    (device compute + gather back to host), ``route_stream`` and
    ``route_mrc`` (the routed chunked-replay and MRC paths, which add
    their own ``stream_chunk_*`` and ``mrc_*`` spans), ``unbatched``
    (``batch=False``'s per-signature engine runs), ``report_solve``,
    ``assembly`` and ``total``.
    """
    if mrc not in ("auto", "off", "require"):
        raise ValueError(
            f"mrc must be 'auto', 'off' or 'require', got {mrc!r}")
    if stream not in ("auto", "off"):
        raise ValueError(f"stream must be 'auto' or 'off', got {stream!r}")
    if report not in ("auto", "batched", "scalar"):
        raise ValueError(
            f"report must be 'auto', 'batched' or 'scalar', got {report!r}")
    if mrc == "require" and not batch:
        raise ValueError(
            "mrc='require' is incompatible with batch=False: the unbatched "
            "path exists as the unbatched engine run")
    if verbose:
        # Convenience for interactive use: make this module's INFO progress
        # lines visible regardless of how (or whether) the app configured
        # logging. verbose=False leaves logging config entirely to the app.
        log.setLevel(logging.INFO)
        if not (log.handlers or logging.getLogger().handlers):
            logging.basicConfig(level=logging.INFO)
    if isinstance(axes, Mapping):
        axes_dict = dict(axes)
        points = expand_grid(axes)
    else:
        axes_dict = {}
        points = [dict(pt) for pt in axes]
    specs = [base.replace(**pt) for pt in points]
    devices = tuple(jax.local_devices() if devices is None else devices)
    solver = ("batched" if batch else "scalar") if report == "auto" else report
    prof: Optional[dict] = None
    if profile:
        stages = (("stream_gen", "traffic_gen", "route_stream", "route_mrc")
                  if batch else ("unbatched",))
        prof = dict.fromkeys(
            stages + ("engine_dispatch_submit", "engine_dispatch_wait",
                      "report_solve", "assembly"), 0.0)
        if batch:
            prof["stream_shared"] = 0
    with span("total", prof):
        reports = _sweep(specs, batch=batch, unroll=unroll, mrc=mrc,
                         stream=stream, solver=solver, donate=donate,
                         devices=devices, prof=prof)
    if prof is not None:
        prof["n_points"] = len(points)
        prof["report_solver"] = solver
    return SweepResult(
        base=base,
        axes=axes_dict,
        points=tuple(points),
        reports=tuple(reports),
        profile=prof,
    )


def _sweep(specs: list[SimSpec], *, batch: bool, unroll: int, mrc: str,
           stream: str, solver: str, donate: bool,
           devices: tuple, prof: Optional[dict]) -> list[SimReport]:
    """:func:`sweep`'s work once its grid is expanded: a report per spec."""
    # One cache run per unique signature.
    sig_of = [spec.cache_signature() for spec in specs]
    unique: dict[tuple, SimSpec] = {}
    for spec, sig in zip(specs, sig_of):
        unique.setdefault(sig, spec)

    counters: dict[tuple, Tier1Counters] = {}
    tenant_ctrs: dict[tuple, TenantCounters] = {}
    if batch:
        # The routed paths generate their streams internally.
        with span("route_stream", prof):
            counters, tenant_ctrs = _route_stream(unique, stream,
                                                  profile=prof)
        if mrc != "off":
            with span("route_mrc", prof):
                counters.update(_route_mrc(
                    {s: sp for s, sp in unique.items() if s not in counters},
                    mrc, profile=prof))
        groups: dict[tuple, list[tuple]] = {}
        for sig, spec in unique.items():
            if sig in counters:  # already served by the MRC path
                continue
            groups.setdefault(_batch_key(spec), []).append(sig)
        # Dispatch everything first (async), then gather: traffic generation
        # and padding for group k+1 overlap device compute for group k, and
        # the queuing solves below overlap the tail of device compute.
        pending: list[_PendingBucket] = []
        # One stream per stream signature for the whole call, across batch
        # groups (a size grid's n_lines buckets share it too).
        streams: dict[tuple, _Stream] = {}
        for key, sigs in groups.items():
            log.info(
                "sweep: batch group n_shards=%d, %d signatures "
                "(n_lines=%d, mapping=%s)",
                key[1], len(sigs), key[0].n_lines, key[2],
            )
            pending.extend(
                _dispatch_group([unique[s] for s in sigs], sigs, streams,
                                unroll=unroll, devices=devices,
                                donate=donate, _prof=prof)
            )
        # Gather blocks on device compute: the wait side of the engine
        # stage (device compute + device→host transfer).
        with span("engine_dispatch_wait", prof):
            for bucket in pending:
                counters.update(bucket.gather())
    else:
        with span("unbatched", prof):
            for sig, spec in unique.items():
                log.info("sweep: run %s", sig)
                counters[sig] = tier1_counters(spec)

    return batched_reports(
        [(spec, counters[sig], tenant_ctrs.get(sig))
         for spec, sig in zip(specs, sig_of)],
        solver=solver, _prof=prof,
    )
