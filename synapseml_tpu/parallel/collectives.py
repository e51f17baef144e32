"""Collective-communication wrappers over the mesh.

The single allreduce stack replacing: LightGBM's native socket ring
(``LGBM_NetworkInit`` + in-C++ histogram allreduce, reference:
NetworkManager.scala:182-205), VW's spanning-tree AllReduce
(VowpalWabbitClusterUtil.scala:16-40) and Horovod's NCCL/Gloo
(dl/utils.py:31-46).  Everything is an XLA collective over ICI/DCN inside
jit — no sockets, no coordinator processes.

Use inside ``shard_map``/``pjit`` bodies with the axis names from
:mod:`synapseml_tpu.parallel.mesh`.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..resilience.faults import get_faults
from ..telemetry import get_registry
from ..telemetry.flight import record as flight_record
from ..telemetry.gangplane import observe_collective
from .mesh import DATA_AXIS


class CollectiveTimeout(RuntimeError):
    """A host-dispatched collective (or the cluster rendezvous) blocked
    past its deadline.

    A rank stuck in an allreduce whose peer died would otherwise freeze
    silently until the gang's global timeout; this converts the freeze
    into a structured failure carrying enough to diagnose it — the op,
    the mesh axis, the per-shard payload, the deadline that expired,
    and (for planner-routed dispatches) the ROUTE: the resolved
    strategy plus the wire phases the compiled program comprises, so a
    watchdogged hierarchical leg names what it was executing
    (``intra_reduce_scatter@f32 | inter_allreduce@int8 | ...``) instead
    of one opaque op name — and the gang supervisor treats it as a
    whole-gang failure (the blocked native dispatch itself cannot be
    cancelled; the raising process exits and the supervisor
    relaunches)."""

    def __init__(self, op: str, axis, timeout_s: float,
                 payload_bytes: Optional[int] = None,
                 strategy: Optional[str] = None,
                 phases: Optional[Sequence[str]] = None):
        extra = (f", {payload_bytes} payload bytes"
                 if payload_bytes is not None else "")
        route = ""
        if strategy is not None:
            route = f" [strategy={strategy}"
            if phases:
                route += " phases=" + " | ".join(phases)
            route += "]"
        super().__init__(
            f"collective {op!r} over axis {axis!r} still blocked after "
            f"{timeout_s:.3f}s{extra}{route}")
        self.op = op
        self.axis = str(axis)
        self.timeout_s = float(timeout_s)
        self.payload_bytes = payload_bytes
        self.strategy = strategy
        self.phases = tuple(phases) if phases else None


class _ShapeOnly:
    """A shape/dtype stand-in leaf for byte accounting — lets the host
    wrapper account S copies of the per-shard LOCAL layout without
    materializing them (``wire_nbytes``/``logical_nbytes`` read only
    ``shape``/``size``/``dtype``)."""
    __slots__ = ("shape", "size", "dtype")

    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.size = int(np.prod(self.shape)) if self.shape else 1
        self.dtype = dtype


def _payload_bytes(x, config=None, channel_major: bool = False) -> int:
    """Per-shard bytes the op actually moves: WIRE bytes when a
    compression config is in play, logical dtype bytes otherwise (the
    pre-codec behavior assumed logical size for every op, which
    double-counted compressed payloads and mis-ranked codecs in
    /metrics and flight events)."""
    from .compression import logical_nbytes, wire_nbytes
    if config is not None and config.compresses:
        return wire_nbytes(x, config, channel_major=channel_major)
    return logical_nbytes(x)


def dispatch_watchdog(fn: Callable, *args, op: str, axis=DATA_AXIS,
                      deadline=None, timeout_s: Optional[float] = None,
                      payload_bytes: Optional[int] = None,
                      codec: str = "none",
                      logical_bytes: Optional[int] = None,
                      strategy: Optional[str] = None,
                      phases: Optional[Sequence[str]] = None, **kw):
    """Run a blocking dispatch under a host-side watchdog timer.

    ``deadline`` (a :class:`~synapseml_tpu.resilience.Deadline`) and/or
    ``timeout_s`` bound the wait; with neither, the call runs inline
    (zero overhead — no thread).  On expiry the caller gets a
    :class:`CollectiveTimeout` and ``collective_timeouts_total{op,axis}``
    ticks; the worker thread stays parked on the un-cancellable native
    call (daemon — it dies with the process, which is the supervisor's
    next move anyway).

    The ``collective.dispatch`` fault site fires INSIDE the watched
    thread, so an armed ``hang`` rule wedges the dispatch exactly where
    a lost peer would.
    """
    # compressed ops tag their flight events with the codec and BOTH
    # byte counts (``nbytes`` is what moved on the wire, ``logical_nbytes``
    # what it represents); planner-routed ops additionally carry the
    # resolved strategy; the bare "none" path emits the identical event
    # payload it always did
    extra = ({"codec": codec, "logical_nbytes": logical_bytes}
             if codec != "none" else {})
    if strategy is not None and strategy != "flat":
        extra["strategy"] = strategy
    seg_strategy = strategy or "flat"
    if deadline is not None:
        timeout_s = deadline.limit(timeout_s)
    if timeout_s is None:
        flight_record("collective.begin", op=op, axis=str(axis),
                      nbytes=payload_bytes, **extra)
        get_faults().raise_point("collective.dispatch", op=op,
                                 axis=str(axis))
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        dt = time.perf_counter() - t0
        flight_record("collective.end", op=op, axis=str(axis),
                      nbytes=payload_bytes, seconds=round(dt, 6), **extra)
        observe_collective(dt, payload_bytes or 0, strategy=seg_strategy)
        return out
    box: dict = {}
    done = threading.Event()

    def _run():
        try:
            get_faults().raise_point("collective.dispatch", op=op,
                                     axis=str(axis))
            box["value"] = fn(*args, **kw)
        except BaseException as e:      # surfaced on the caller's thread
            box["error"] = e
        finally:
            done.set()

    flight_record("collective.begin", op=op, axis=str(axis),
                  nbytes=payload_bytes, timeout_s=float(timeout_s), **extra)
    t0 = time.perf_counter()
    t = threading.Thread(target=_run, daemon=True,
                         name=f"collective-{op}")
    t.start()
    if not done.wait(timeout=max(0.0, float(timeout_s))):
        get_registry().counter(
            "collective_timeouts_total",
            "host-dispatched collectives that blocked past their "
            "deadline", ("op", "axis")).inc(1, op=op, axis=str(axis))
        flight_record("collective.timeout", op=op, axis=str(axis),
                      nbytes=payload_bytes, timeout_s=float(timeout_s),
                      **extra)
        raise CollectiveTimeout(op, axis, float(timeout_s),
                                payload_bytes=payload_bytes,
                                strategy=strategy, phases=phases)
    dt = time.perf_counter() - t0
    if "error" in box:
        # failed collectives leave the `begin` unpaired, matching the
        # inline leg — a paired `end` means the op completed
        raise box["error"]
    flight_record("collective.end", op=op, axis=str(axis),
                  nbytes=payload_bytes, seconds=round(dt, 6), **extra)
    observe_collective(dt, payload_bytes or 0, strategy=seg_strategy)
    return box["value"]


def _record(op: str, axis, x, config=None, channel_major: bool = False,
            strategy: str = "flat") -> None:
    """EQuARX-style per-collective accounting (arXiv:2506.17615): count +
    payload bytes per (op, axis) into the process metrics registry.
    ``collective_bytes_total`` stays LOGICAL bytes (the signal the op
    reduces); compressed ops additionally land their WIRE bytes +
    compression ratio via :func:`~synapseml_tpu.parallel.compression.
    record_compressed` so codecs rank correctly in /metrics.

    These wrappers run under jit TRACING, so for compiled code each
    series counts collectives per traced program, weighted by the
    per-shard payload the op moves — the number that answers "how many
    bytes does this step's program hand to the ICI" — not per execution.
    Telemetry must never break a trace, hence the blanket except."""
    try:
        from .compression import logical_nbytes, record_compressed
        nbytes = logical_nbytes(x)
        reg = get_registry()
        labels = dict(op=op, axis=str(axis))
        reg.counter("collective_calls_total",
                    "collective ops traced, by op and mesh axis",
                    ("op", "axis")).inc(1, **labels)
        reg.counter("collective_bytes_total",
                    "per-shard LOGICAL payload bytes handed to "
                    "collectives, by op and mesh axis", ("op", "axis")).inc(
                        nbytes, **labels)
        if config is not None and config.compresses:
            record_compressed(op, axis, x, config,
                              channel_major=channel_major,
                              strategy=strategy)
    except Exception:
        pass


def psum(x, axis: str = DATA_AXIS):
    _record("psum", axis, x)
    return lax.psum(x, axis_name=axis)


def pmean(x, axis: str = DATA_AXIS):
    _record("pmean", axis, x)
    return lax.pmean(x, axis_name=axis)

def pmax(x, axis: str = DATA_AXIS):
    _record("pmax", axis, x)
    return lax.pmax(x, axis_name=axis)


def pmin(x, axis: str = DATA_AXIS):
    _record("pmin", axis, x)
    return lax.pmin(x, axis_name=axis)


def all_gather(x, axis: str = DATA_AXIS, *, tiled: bool = False):
    _record("all_gather", axis, x)
    return lax.all_gather(x, axis_name=axis, tiled=tiled)


def reduce_scatter(x, axis: str = DATA_AXIS, *, scatter_dimension: int = 0):
    _record("reduce_scatter", axis, x)
    return lax.psum_scatter(x, axis_name=axis,
                            scatter_dimension=scatter_dimension, tiled=True)


def ppermute(x, perm: Sequence[tuple], axis: str = DATA_AXIS):
    _record("ppermute", axis, x)
    return lax.ppermute(x, axis_name=axis, perm=list(perm))


def ring_shift(x, axis: str = DATA_AXIS, *, reverse: bool = False):
    """Send to the next rank on the ring (the ring-attention building block)."""
    _record("ring_shift", axis, x)
    n = lax.axis_size(axis)
    if reverse:
        perm = [(i, (i - 1) % n) for i in range(n)]
    else:
        perm = [(i, (i + 1) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: str = DATA_AXIS):
    return lax.axis_index(axis)


def barrier(x, axis: str = DATA_AXIS):
    """Gang sync inside a mapped computation — the
    ``BarrierTaskContext.barrier()`` analogue (NetworkManager.scala:150-156).

    Returns ``x`` data-dependent on a cross-replica collective, so XLA cannot
    reorder work on ``x`` before the sync or dead-code-eliminate the
    collective (a bare unused psum would be DCE'd)."""
    _record("barrier", axis, jnp.ones((), jnp.int32))
    token = lax.psum(jnp.ones((), jnp.int32), axis_name=axis)
    gated, _ = lax.optimization_barrier((x, token))
    return gated


def shard_map_over(mesh: Mesh, in_specs, out_specs,
                   check_vma: bool = False) -> Callable:
    """Decorator: shard_map a function over ``mesh`` with the given specs."""
    def wrap(fn):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)
    return wrap


def ring_allreduce(x, axis: str = DATA_AXIS):
    """Explicit bandwidth-optimal ring allreduce: reduce-scatter around the
    ring then all-gather back, each step moving 1/n of the payload to the
    next neighbor — the algorithm LightGBM's socket ring implements in C++
    (the native allreduce behind LGBM_NetworkInit, NetworkManager.scala:188)
    and the schedule XLA itself lowers ``psum`` to on a 1-D link.  Exposed
    explicitly for (a) parity tests pinning our semantics to the
    reference's, and (b) composing with compute between the 2(n-1) steps
    (latency hiding) where a monolithic psum could not.

    ``x``: equal-shape per-rank value whose leading dim is divisible by the
    axis size.  Returns the SUM over ranks, replicated (== lax.psum).
    """
    _record("ring_allreduce", axis, x)
    return _ring_core(x, axis, int(lax.axis_size(axis)))


def _ring_core(x, axis, n: int):
    """The unrecorded ring schedule :func:`ring_allreduce` documents —
    shared with the collective planner's ``ring`` strategy
    (:mod:`~synapseml_tpu.parallel.planner`), which does its own
    strategy-labeled accounting."""
    if n == 1:
        return x
    me = lax.axis_index(axis)
    parts = jnp.stack(jnp.split(x, n, axis=0))         # (n, chunk, ...)
    to_next = [(i, (i + 1) % n) for i in range(n)]

    # reduce-scatter: after n-1 steps rank r owns the full sum of part
    # (r+1) mod n
    def rs_step(s, acc):
        # send the partial we just finished accumulating
        idx = (me - s) % n
        sending = acc[idx]
        received = lax.ppermute(sending, axis_name=axis, perm=to_next)
        return acc.at[(me - s - 1) % n].add(received)

    acc = lax.fori_loop(0, n - 1, rs_step, parts)
    own = (me + 1) % n

    # all-gather: circulate each finished part the rest of the way round
    def ag_step(s, st):
        acc, moving = st
        received = lax.ppermute(moving, axis_name=axis, perm=to_next)
        acc = acc.at[(own - s - 1) % n].set(received)
        return acc, received

    acc, _ = lax.fori_loop(0, n - 1, ag_step, (acc, acc[own]))
    return jnp.concatenate(list(acc), axis=0)


def hierarchical_psum(x, inner_axis: str, outer_axis: str):
    """Two-level allreduce for multi-slice meshes: reduce-scatter over the
    fast ``inner_axis`` (ICI within a slice), psum the 1/n-sized shard over
    the slow ``outer_axis`` (DCN between slices), then all-gather back over
    ICI — cross-DCN traffic shrinks by the inner axis size versus a flat
    psum over both axes.  Leading dim must divide the inner axis size.
    Returns the global sum, replicated on both axes (== psum over both)."""
    _record("hierarchical_psum", f"{inner_axis}+{outer_axis}", x)
    scattered = lax.psum_scatter(x, axis_name=inner_axis,
                                 scatter_dimension=0, tiled=True)
    scattered = lax.psum(scattered, axis_name=outer_axis)
    return lax.all_gather(scattered, axis_name=inner_axis, tiled=True)


def tree_psum_bucketed(tree, axis: str = DATA_AXIS,
                       bucket_bytes: int = 4 << 20):
    """psum a pytree (gradients) in size-bucketed fusion groups: leaves are
    packed into ~``bucket_bytes`` flat buffers so small tensors ride one
    collective (latency-bound regime) while huge ones keep their own
    (bandwidth-bound regime) — Horovod's tensor-fusion strategy
    (the NCCL path behind dl/utils.py:31-46) expressed in XLA."""
    _record("tree_psum_bucketed", axis, tree)
    leaves, treedef = jax.tree.flatten(tree)
    # buckets are per-dtype so the fused buffer sums at each leaf's OWN
    # precision — a float32 detour would silently round f64/int leaves
    buckets: list = []
    cur: list = []
    cur_bytes = 0
    cur_dtype = None
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * leaf.dtype.itemsize
        if cur and (cur_bytes + nbytes > bucket_bytes
                    or leaf.dtype != cur_dtype):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = leaf.dtype
    if cur:
        buckets.append(cur)
    out = list(leaves)
    for bucket in buckets:
        if len(bucket) == 1:
            i = bucket[0]
            out[i] = lax.psum(leaves[i], axis_name=axis)
            continue
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in bucket])
        summed = lax.psum(flat, axis_name=axis)
        offset = 0
        for i in bucket:
            size = leaves[i].size
            out[i] = summed[offset:offset + size].reshape(leaves[i].shape)
            offset += size
    return jax.tree.unflatten(treedef, out)


def allreduce_fn(mesh: Mesh, axis: str = DATA_AXIS,
                 config=None) -> Callable:
    """jitted allreduce over the data axis: input is per-rank values stacked
    on dim 0 (shape (num_ranks, *H)), output is their sum (shape (*H)).
    The LightGBM histogram-allreduce replacement.

    ``config`` (a :class:`~synapseml_tpu.parallel.compression.
    CollectiveConfig`) selects the wire codec: the reduce runs as the
    compressed :func:`~synapseml_tpu.parallel.compression.
    compressed_psum`, and every metric/flight event reports WIRE bytes
    with the codec attached (``None``/"none" keeps today's f32 path and
    event payloads byte-identical).

    The returned callable is host-dispatched (unlike the in-jit wrappers
    above), so each call ALSO lands one sample in the
    ``collective_latency_seconds`` histogram — dispatch latency under
    async execution, true op latency when the caller synchronizes.

    Hang-proofing: pass ``deadline=`` (a :class:`~synapseml_tpu.
    resilience.Deadline`) or ``timeout_s=`` per call and an
    indefinitely-blocked dispatch raises :class:`CollectiveTimeout`
    instead of freezing the rank (see :func:`dispatch_watchdog`)."""
    from .compression import codec_eligible, record_compressed
    from .planner import planned_psum
    compresses = config is not None and config.compresses
    codec = config.compression if compresses else "none"

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(axis), out_specs=P(), check_vma=False)
    def _allreduce(x):
        # x.sum(0) handles both one and several stacked values per shard.
        # record=False: the host wrapper below accounts this op once
        # (per call, on the full stacked payload) — recording the
        # traced inner reduce too would double-count the series.
        # planned_psum resolves the route at trace time; config=None and
        # strategy-flat configs delegate to the exact pre-planner
        # dispatch (compressed_psum / bare lax.psum), byte-identically.
        local = x.sum(0)
        return planned_psum(local, axis, config, op="allreduce_fn",
                            record=False)

    latency = get_registry().histogram(
        "collective_latency_seconds",
        "host-observed latency of host-dispatched collectives",
        ("op", "axis"))
    #: payload signature -> ReductionPlan (or None), resolved at the
    #: FIRST dispatch of each signature — exactly when jit traces it —
    #: and pinned, so the host labels keep naming the route the
    #: already-compiled program runs even after a planner refresh or
    #: set_spec re-routes plans for signatures not yet traced
    plans: dict = {}

    @functools.wraps(_allreduce)
    def timed(x, *, deadline=None, timeout_s=None):
        # codec accounting shares the traced compressed_psum's
        # eligibility predicate: the codec applies to the locally summed
        # (*H,) payload, so a stacked input whose inner size is below
        # min_size (or non-float) really reduces in f32 and must be
        # reported that way — not as int8 wire that never existed
        inner = getattr(x, "shape", ())[1:]
        dtype = getattr(x, "dtype", jnp.float32)
        # the planner resolves the ROUTE the traced body takes for this
        # payload class — same planner, same cache key as the traced
        # planned_psum, resolved once per payload signature (the jit
        # cache key) and pinned in ``plans``, so the host-side labels
        # (strategy on metrics, flight events, StepProfiler segment,
        # CollectiveTimeout phases) name the route the compiled program
        # really runs even after a mid-life planner refresh/set_spec
        sig = (tuple(getattr(x, "shape", ())), str(np.dtype(dtype)))
        if sig in plans:
            plan = plans[sig]
        else:
            plan = None
            if config is not None and config.strategy != "flat":
                from .planner import get_planner
                nbytes = (int(np.prod(inner)) if inner else 1) \
                    * np.dtype(dtype).itemsize
                plan = get_planner().plan(nbytes, int(mesh.shape[axis]),
                                          config, axis=str(axis),
                                          op="allreduce_fn")
            plans[sig] = plan
        routed = plan is not None and plan.strategy != "flat"
        strategy = plan.strategy if routed else "flat"
        active = codec_eligible(inner, dtype, config)
        # a routed plan may demote the codec for its route (tree runs
        # latency-bound payloads at the logical dtype)
        eff_codec = (plan.wire_codec(tuple(inner), dtype) if routed
                     else (codec if active else "none"))
        wire_active = eff_codec != "none"
        # the traced compressed_psum lays the ndim>=2 LOCAL (*H) out
        # channel-major (per-channel chunk padding), so the stacked
        # account is S x the padded local — padding the stacked array
        # itself would miscount the pad bytes the wire really ships
        cm = len(inner) >= 2
        if wire_active:
            S = int(getattr(x, "shape", (1,))[0])
            payload = [_ShapeOnly(inner, dtype)] * S
        else:
            payload = x
        if routed:
            # calls/logical series, then the strategy-labeled wire
            # series at the codec and bytes the route REALLY ships
            # (uncompressed routes land wire == logical so the
            # per-strategy wire histogram covers f32 routes too;
            # hierarchical counts its intra-host f32 legs — see
            # ReductionPlan.wire_nbytes)
            wire = plan.wire_nbytes(payload, eff_codec,
                                    channel_major=cm)
            _record("allreduce_fn", axis, payload)
            record_compressed("allreduce_fn", axis, payload,
                              config if wire_active else None,
                              channel_major=cm, strategy=strategy,
                              codec=eff_codec, wire=wire)
        else:
            _record("allreduce_fn", axis, payload,
                    config=config if wire_active else None,
                    channel_major=cm, strategy=strategy)
            wire = _payload_bytes(payload,
                                  config if wire_active else None,
                                  channel_major=cm)
        extra = ({"codec": eff_codec, "logical_nbytes": _payload_bytes(x)}
                 if wire_active else {})
        if routed:
            extra["strategy"] = strategy
        t0 = time.perf_counter()
        if deadline is None and timeout_s is None:
            out = _allreduce(x)
            # host-observed dispatch latency feeds the open train step's
            # collective segment + the flight ring (the watched leg below
            # goes through dispatch_watchdog, which does both itself)
            dt = time.perf_counter() - t0
            observe_collective(dt, wire, strategy=strategy)
            flight_record("collective.end", op="allreduce_fn",
                          axis=str(axis), nbytes=wire,
                          seconds=round(dt, 6), **extra)
        else:
            # the watched leg must SYNCHRONIZE: under async dispatch the
            # bare call returns before the ring moves a byte, and a hung
            # collective would block some later consumer instead of here
            out = dispatch_watchdog(
                lambda v: jax.block_until_ready(_allreduce(v)), x,
                op="allreduce_fn", axis=axis,
                deadline=deadline, timeout_s=timeout_s,
                payload_bytes=wire, codec=eff_codec,
                logical_bytes=_payload_bytes(x),
                strategy=strategy if routed else None,
                phases=plan.phases(eff_codec) if routed else None)
        latency.observe(time.perf_counter() - t0, op="allreduce_fn",
                        axis=str(axis))
        return out

    return timed
