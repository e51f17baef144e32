"""Topology-aware collective planner: per-payload reduction routing.

The PR 6 codecs decide *what bytes* ride a reduction; nothing decided
*what route* they take — every reduction was whatever ``jax.lax`` emits,
whether the gang spans one ICI-connected host or many DCN-separated
ones.  This module synthesizes a :class:`ReductionPlan` per payload —
**ring** (bandwidth-optimal reduce-scatter + all-gather around the
axis), **tree** (recursive-doubling exchange, ``log2(n)`` rounds —
latency-optimal for small payloads, Horovod's size-dependent selection,
arXiv:1802.05799), or **two-level hierarchical** (intra-host
reduce-scatter in f32, inter-host allreduce through the PR 6 int8/bf16
codecs, intra-host all-gather back — EQuARX, arXiv:2506.17615) — chosen
from payload bytes × world size × link class, behind the existing
:class:`~synapseml_tpu.parallel.compression.CollectiveConfig`
(``strategy='auto'|'flat'|'ring'|'tree'|'hierarchical'``).

Honesty contract (the roofline spec-table pattern): the ``auto``
decision table only routes away from ``flat`` when the topology is
actually KNOWN — device mesh coords discovered from the backend, or an
explicitly injected :class:`TopologySpec` (CPU-container tests).  An
unknown topology plans ``flat``, byte-identical to the
pre-planner dispatch; nothing is fabricated.

Plans bind at TRACE time (the planner runs while jit traces, like the
``_record`` accounting), are cached in size buckets keyed like jit
statics ``(payload bucket, world, config, spec, epoch)``, and the cache
is invalidated at every :class:`~synapseml_tpu.parallel.supervisor.
GangSupervisor` relaunch/resize boundary (world size changed → topology
snapshot refreshed → plans rebuilt; already-compiled programs keep
their traced route — gang workers are fresh processes, so the refresh
lands with the relaunch).

Telemetry: ``collective_plans_total{strategy,reason,model}`` per
synthesized plan (``model`` names what priced the auto decision —
``fitted`` a measured α-β fit from the tuning table, ``spec`` the
hardcoded cutoff constants, ``fallback`` no cost model consulted at
all: forced strategies, single rank, unknown topology),
``plan_decide``/``plan_invalidate`` flight events, the
``collective_wire_bytes_total{op,axis,codec,strategy}`` strategy label,
and the StepProfiler collective segment split by strategy — every
routing choice is attributable in /metrics and flight rings.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Dict, Optional, Tuple

from ..resilience.faults import get_faults
from ..telemetry import get_registry
from ..telemetry.flight import record as flight_record

__all__ = ["TopologySpec", "ReductionPlan", "CollectivePlanner",
           "STRATEGIES", "TREE_CUTOFF_BYTES", "get_planner", "set_planner",
           "planned_psum", "PLANNER_METRICS"]

#: strategies a :class:`~synapseml_tpu.parallel.compression.
#: CollectiveConfig` may request ('auto' resolves per payload)
STRATEGIES = ("auto", "flat", "ring", "tree", "hierarchical")

#: payloads at or below this ride the latency-optimal tree under 'auto'
#: (the Horovod ring-vs-tree crossover class: log2(n) full-payload sends
#: beat 2(n-1) chunked hops only while the per-hop latency dominates)
TREE_CUTOFF_BYTES = 256 << 10

#: planner-level metric names (held to the docs bar by
#: tests/test_collective_planner.py, the GANG_METRICS pattern)
PLANNER_METRICS = frozenset({"collective_plans_total"})

#: aggregate per-chip ICI bytes/s by device kind (public spec sheets) —
#: carried on discovered specs for telemetry context and link-class
#: RANKING only (the decision table is structural); absent kinds stay
#: None: unknown backend ⇒ claim nothing (telemetry.roofline pattern)
CHIP_ICI_BW = {
    "TPU v4": 300e9,
    "TPU v5 lite": 200e9,    # v5e
    "TPU v5": 600e9,         # v5p
    "TPU v6 lite": 450e9,    # v6e / Trillium
}


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """The link structure plans are routed by.

    Frozen + hashable on purpose: it joins the plan-cache key exactly
    like a jit static.  ``source='discovered'`` specs are built from the
    live :func:`~synapseml_tpu.parallel.topology.get_topology` snapshot;
    ``'injected'`` specs are explicit overrides (CPU-container tests,
    synthetic topologies) and are always trusted.
    """
    n_hosts: int = 1
    devices_per_host: int = 1
    platform: str = "unknown"
    #: every device reported chip mesh coords (real ICI structure seen)
    coords_known: bool = False
    #: link-class context (bytes/s); None = unknown, never guessed
    ici_bytes_per_s: Optional[float] = None
    dcn_bytes_per_s: Optional[float] = None
    source: str = "injected"

    def __post_init__(self):
        if self.n_hosts < 1 or self.devices_per_host < 1:
            raise ValueError(
                f"TopologySpec needs n_hosts >= 1 and devices_per_host "
                f">= 1, got {self.n_hosts}x{self.devices_per_host}")
        if self.source not in ("injected", "discovered"):
            raise ValueError(f"source={self.source!r}")

    @property
    def world(self) -> int:
        return self.n_hosts * self.devices_per_host

    @property
    def multi_host(self) -> bool:
        return self.n_hosts > 1

    @property
    def trusted(self) -> bool:
        """May 'auto' route on this spec?  Injected specs always;
        discovered ones only when the backend really exposed coords —
        a CPU/host-platform snapshot stays untrusted so every default
        path keeps planning ``flat`` (no fabricated topology)."""
        return self.source == "injected" or self.coords_known


def discover_spec() -> TopologySpec:
    """Build a ``source='discovered'`` spec from the live jax topology
    (imports jax — call only where jax is already the runtime)."""
    from .topology import get_topology
    import jax
    topo = get_topology()
    ici = None
    try:
        from ..telemetry.roofline import chip_lookup
        ici = chip_lookup(jax.devices()[0], CHIP_ICI_BW)
    except Exception:
        ici = None
    n_slices = topo.num_slices()
    n_hosts = max(topo.num_processes, n_slices or 1)
    return TopologySpec(
        n_hosts=n_hosts,
        devices_per_host=max(1, topo.num_devices // max(1, n_hosts)),
        platform=topo.platform,
        coords_known=topo.coords_known,
        ici_bytes_per_s=ici,
        dcn_bytes_per_s=None,
        source="discovered")


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def _bucket(nbytes: int) -> int:
    """Size bucket of a payload: next power of two (plans for 1.1 MB and
    1.9 MB share one cache entry — the prefill-bucket idiom applied to
    the plan cache)."""
    nbytes = max(1, int(nbytes))
    return 1 << (nbytes - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class ReductionPlan:
    """One resolved route for one (payload bucket, world, config).

    Frozen + hashable (it rides trace-time closures and cache keys).
    ``execute`` has ``psum`` semantics — per-shard value in, replicated
    sum out — and MUST run inside shard_map tracing over ``axis``;
    ``reduce_flat`` is the gradient-stream form the DL sync uses
    (padded flat f32 in, (total, this-rank's-quantization-error) out).
    """
    strategy: str                 # resolved: flat | ring | tree | hierarchical
    reason: str                   # why the decision table chose it
    world: int
    inner: int                    # intra-host group size (hierarchical; else world)
    payload_bucket: int
    config: Any = None            # the CollectiveConfig (or None = bare flat)

    @property
    def outer(self) -> int:
        return self.world // max(1, self.inner)

    def wire_codec(self, shape, dtype) -> str:
        """The codec THIS plan puts on its quantized leg for a payload
        of this shape — 'none' when the config doesn't compress it, and
        for ``tree`` routes (latency-bound payloads ride the logical
        dtype; chunked int8 would add two codec passes to save bytes
        that don't matter at this size — bf16 still composes)."""
        from .compression import codec_eligible
        cfg = self.config
        if cfg is None or not codec_eligible(shape, dtype, cfg):
            return "none"
        if self.strategy == "tree" and cfg.compression == "int8":
            return "none"
        return cfg.compression

    def pad_unit(self, codec: str) -> int:
        """Flat-stream padding multiple the route needs (static)."""
        if self.strategy == "ring":
            return (self.world * self.config.chunk if codec == "int8"
                    else self.world)
        if self.strategy == "hierarchical":
            return (self.inner * self.config.chunk if codec == "int8"
                    else self.inner)
        if self.strategy == "flat" and codec == "int8":
            return self.world * self.config.chunk
        return 1

    def wire_nbytes(self, x, codec: str,
                    channel_major: bool = False) -> int:
        """Per-shard bytes THIS route actually puts on the wire for
        ``x``.  flat/ring/tree follow the one-payload-traversal
        convention the flat accounting already uses (at the route's
        EFFECTIVE codec — a tree that demoted int8 reports f32 wire,
        not int8 wire that never existed).  hierarchical counts its
        real legs: two intra-host f32 passes (reduce-scatter +
        all-gather, ``(inner-1)/inner`` of the payload each) plus the
        ``1/inner`` inter-host shard at codec width — pricing the whole
        payload at int8 width would claim a ~4x wire win the f32
        intra-host legs don't deliver."""
        from .compression import logical_nbytes, wire_nbytes
        live = self.config if codec != "none" else None
        if self.strategy != "hierarchical":
            return wire_nbytes(x, live, channel_major=channel_major)
        logical = logical_nbytes(x)
        intra = 2 * (self.inner - 1) * logical // self.inner
        inter = wire_nbytes(x, live,
                            channel_major=channel_major) // self.inner
        return intra + inter

    def phases(self, codec: str = "none") -> Tuple[str, ...]:
        """The wire legs a dispatch under this plan comprises — attached
        to :class:`~synapseml_tpu.parallel.collectives.CollectiveTimeout`
        payloads so a watchdogged hierarchical leg names what it was
        executing instead of one opaque op name."""
        if self.strategy == "hierarchical":
            return ("intra_reduce_scatter@f32",
                    f"inter_allreduce@{codec}",
                    "intra_all_gather@f32")
        if self.strategy == "ring":
            return (f"ring_reduce_scatter@{codec if codec != 'none' else 'f32'}",
                    f"ring_all_gather@{codec if codec != 'none' else 'f32'}")
        if self.strategy == "tree":
            return (f"tree_exchange@{codec if codec != 'none' else 'f32'}",)
        if codec == "int8":
            return ("reduce_scatter@int8", "all_gather@int8")
        return (f"psum@{codec if codec != 'none' else 'f32'}",)

    # -- execution (trace-time jax; imports deferred so the planner is
    # importable driver-side without jax) --------------------------------

    def execute(self, x, axis, op: str = "planned_psum",
                record: bool = True):
        """``psum`` semantics under this plan's route.  ``flat``
        delegates verbatim to :func:`~synapseml_tpu.parallel.
        compression.compressed_psum` — byte-identical tracing to the
        pre-planner dispatch, by construction."""
        from .compression import compressed_psum
        if self.strategy == "flat":
            return compressed_psum(x, axis, self.config, op=op,
                                   record=record)
        import jax.numpy as jnp
        from .compression import (_channel_major_padded,
                                  _channel_major_padded_inv, _pad_to)
        codec = self.wire_codec(x.shape, x.dtype)
        if record:
            _record_routed(op, axis, x, self, codec)
        shape, orig_dtype = x.shape, x.dtype
        if codec == "none":
            # route at the input dtype (ints stay ints; addition is the
            # reduction either way — a detour through f32 would round
            # int payloads past 2^24)
            flat = x.reshape(-1)
            size = flat.shape[0]
            flat = _pad_to(flat, self.pad_unit(codec))
            total, _ = self.reduce_flat(flat, axis, codec, want_err=False)
            return total[:size].reshape(shape)
        # codec legs run f32 like compressed_psum; int8 chunks are laid
        # out channel-major so heterogeneous trailing channels (GBDT
        # grad/hess/count) never share a scale
        cm = codec == "int8"
        if cm:
            flat, per, per_p = _channel_major_padded(
                x.astype(jnp.float32), self.config.chunk)
        else:
            flat, per, per_p = x.astype(jnp.float32).reshape(-1), None, None
        size = flat.shape[0]
        flat = _pad_to(flat, self.pad_unit(codec))
        total, _ = self.reduce_flat(flat, axis, codec, want_err=False)
        total = total[:size]
        if cm:
            return _channel_major_padded_inv(total, shape, per,
                                             per_p).astype(orig_dtype)
        return total.reshape(shape).astype(orig_dtype)

    def reduce_flat(self, flat, axis, codec: str, want_err: bool = False):
        """Sum a padded flat stream over ``axis`` along this route →
        ``(total, err)``.

        ``err`` (only materialized when ``want_err``) is THIS rank's
        share of the wire quantization error, in the stream's
        coordinates — the error-feedback recursion's input.  The EF
        invariant is the SUM across ranks: for flat/ring codecs each
        rank keeps its own payload's error; for hierarchical each rank
        keeps the error of the intra-host shard it owned on the
        quantized inter-host leg (zero elsewhere), so
        ``sum_r err_r == total quantization error`` exactly — per-leaf
        error feedback composes unchanged.
        """
        import jax.numpy as jnp
        from jax import lax
        from .compression import (bf16_decode, bf16_encode, int8_all_gather,
                                  int8_decode, int8_encode,
                                  int8_reduce_scatter)
        from .collectives import _ring_core
        cfg = self.config
        n = self.world
        zeros = (lambda: jnp.zeros_like(flat)) if want_err else (lambda: None)

        if self.strategy == "hierarchical":
            return self._hier_reduce_flat(flat, axis, codec, want_err)

        if codec == "int8":
            # flat AND ring: the chunked int8 reduce-scatter +
            # all-gather IS the bandwidth-optimal ring schedule — the
            # 'ring' label names the route it already takes
            total = int8_all_gather(
                int8_reduce_scatter(flat, axis, cfg.chunk), axis, cfg.chunk)
            if want_err:
                err = flat - int8_decode(*int8_encode(flat, cfg.chunk))
                return total, err
            return total, None
        if codec == "bf16":
            enc = bf16_encode(flat)
            if self.strategy == "ring":
                total = bf16_decode(_ring_core(enc, axis, n))
            elif self.strategy == "tree":
                total = bf16_decode(self._tree_core(enc, axis))
            else:
                total = bf16_decode(lax.psum(enc, axis_name=axis))
            if want_err:
                return total, flat - bf16_decode(enc)
            return total, None
        # f32 / logical-dtype routes (lossless: err stays zero)
        if self.strategy == "ring":
            return _ring_core(flat, axis, n), zeros()
        if self.strategy == "tree":
            return self._tree_core(flat, axis), zeros()
        return lax.psum(flat, axis_name=axis), zeros()

    def _tree_core(self, v, axis):
        """Recursive-doubling allreduce: log2(world) pairwise
        exchange-and-add rounds (partner = rank XOR 2^k).  Every rank
        sums the same balanced tree shape (operand order differs only
        commutatively), so the result is replicated bit-identically."""
        from jax import lax
        n = self.world
        k = 1
        while k < n:
            perm = [(i, i ^ k) for i in range(n)]
            v = v + lax.ppermute(v, axis, perm=perm)
            k <<= 1
        return v

    def _groups(self):
        """Intra-host rank blocks + the transposed inter-host groups,
        carved by the same assignment core that places data partitions
        (:func:`~synapseml_tpu.parallel.placement.partition_assignment`
        — placement and reduction grouping cannot drift apart)."""
        from .placement import partition_assignment
        pm = partition_assignment(self.world, self.outer, strategy="block")
        intra = [pm.rank_to_partitions[h] for h in range(self.outer)]
        inter = [[intra[h][i] for h in range(self.outer)]
                 for i in range(self.inner)]
        return intra, inter

    def _hier_reduce_flat(self, flat, axis, codec: str, want_err: bool):
        """Two-level allreduce over one gang axis via grouped
        collectives: intra-host reduce-scatter in f32 (ICI), inter-host
        allreduce through the codec (DCN — the only leg that crosses
        hosts ships 1/inner of the payload, quantized), intra-host
        all-gather back in f32."""
        import jax.numpy as jnp
        from jax import lax
        from .compression import (bf16_decode, bf16_encode, int8_decode,
                                  int8_encode)
        intra, inter = self._groups()
        shard = lax.psum_scatter(flat, axis, scatter_dimension=0,
                                 tiled=True, axis_index_groups=intra)
        err_shard = None
        if codec == "int8":
            q, s = int8_encode(shard, self.config.chunk)
            qg = lax.all_gather(q, axis_name=axis, axis_index_groups=inter)
            sg = lax.all_gather(s, axis_name=axis, axis_index_groups=inter)
            total_shard = jnp.sum(
                qg.astype(jnp.float32) * sg[..., None], axis=0).reshape(-1)
            if want_err:
                err_shard = shard - int8_decode(q, s)
        elif codec == "bf16":
            enc = bf16_encode(shard)
            total_shard = bf16_decode(
                lax.psum(enc, axis_name=axis, axis_index_groups=inter))
            if want_err:
                err_shard = shard - bf16_decode(enc)
        else:
            total_shard = lax.psum(shard, axis_name=axis,
                                   axis_index_groups=inter)
        out = lax.all_gather(total_shard, axis_name=axis, tiled=True,
                             axis_index_groups=intra)
        if not want_err:
            return out, None
        if err_shard is None:
            return out, jnp.zeros_like(flat)
        # this rank owned shard (me % inner) of its host's sum on the
        # quantized leg: keep exactly that error, zero elsewhere —
        # summing residuals across the gang reproduces the total error
        me = lax.axis_index(axis)
        shard_len = flat.shape[0] // self.inner
        err = lax.dynamic_update_slice(
            jnp.zeros_like(flat), err_shard,
            ((me % self.inner) * shard_len,))
        return out, err


def _record_routed(op: str, axis, x, plan: "ReductionPlan",
                   codec: str) -> None:
    """Trace-time accounting for a routed (non-flat) collective: the
    plain calls/logical series plus the strategy-labeled wire series at
    the bytes the ROUTE really ships (:meth:`ReductionPlan.wire_nbytes`
    — codec='none' routes report wire == logical, hierarchical counts
    its intra-host f32 legs), so the per-strategy wire series covers
    uncompressed routes too.  Telemetry must never break a
    trace."""
    try:
        from .collectives import _record
        from .compression import record_compressed
        _record(op, axis, x)            # collective_{calls,bytes}_total
        cm = codec == "int8"
        record_compressed(op, axis, x,
                          plan.config if codec != "none" else None,
                          channel_major=cm, strategy=plan.strategy,
                          codec=codec,
                          wire=plan.wire_nbytes(x, codec,
                                                channel_major=cm))
    except Exception:
        pass


class CollectivePlanner:
    """Process-global plan synthesizer + size-bucketed cache.

    Thread-safe.  The cache key is ``(payload bucket, world, config,
    spec, epoch)`` — every component hashable, exactly the jit-statics
    discipline, so a topology refresh (epoch bump) or a spec swap can
    never serve a stale route to a NEW trace."""

    def __init__(self, spec: Optional[TopologySpec] = None):
        self._lock = threading.RLock()
        self._injected = spec
        self._discovered: Optional[TopologySpec] = None
        self._discovery_failed = False
        self._epoch = 0
        self._plans: Dict[Tuple, ReductionPlan] = {}
        self._c_plans = get_registry().counter(
            "collective_plans_total",
            "reduction plans synthesized, by resolved strategy, decision "
            "reason and the cost model that priced the auto decision "
            "(fitted|spec|fallback)", ("strategy", "reason", "model"))
        #: resolved once per epoch: a measured α-β fit from the tuning
        #: table when one matches this device, else the spec-constant
        #: model (byte-identical decisions to the hardcoded cutoff)
        self._cost_model: Optional[Any] = None

    # -- topology ----------------------------------------------------------
    def spec(self) -> Optional[TopologySpec]:
        """The spec plans route by: the injected override when set, else
        a lazily discovered snapshot (None when discovery fails — e.g.
        planner used driver-side before jax initializes)."""
        with self._lock:
            if self._injected is not None:
                return self._injected
            if self._discovered is None and not self._discovery_failed:
                try:
                    self._discovered = discover_spec()
                except Exception:
                    self._discovery_failed = True
            return self._discovered

    def set_spec(self, spec: Optional[TopologySpec],
                 reason: str = "injected") -> None:
        """Inject (or with ``None`` clear) the topology override;
        invalidates every cached plan."""
        with self._lock:
            self._injected = spec
            self._invalidate(reason)

    def refresh(self, reason: str, world_size: Optional[int] = None) -> None:
        """The relaunch/resize hook: drop the discovered topology
        snapshot (next plan re-discovers) and every cached plan.  An
        injected spec survives — it is an explicit operator/test
        override, not a snapshot.  Records the invalidation in the
        fault call log and the flight ring so resize tests can pin
        that a resize really re-planned."""
        with self._lock:
            self._discovered = None
            self._discovery_failed = False
            self._invalidate(reason, world_size=world_size)

    def _invalidate(self, reason: str,
                    world_size: Optional[int] = None) -> None:
        dropped = len(self._plans)
        self._plans.clear()
        self._cost_model = None          # re-consult the table next plan
        self._epoch += 1
        get_faults().note("plan.refresh", reason=reason,
                          world_size=world_size, dropped_plans=dropped,
                          epoch=self._epoch)
        try:
            flight_record("plan_invalidate", reason=reason,
                          world_size=world_size, dropped_plans=dropped,
                          epoch=self._epoch)
        except Exception:
            pass

    # -- cost model --------------------------------------------------------
    def cost_model(self):
        """The :class:`~synapseml_tpu.telemetry.autotune.
        CollectiveCostModel` pricing this planner's 'auto' decisions:
        a measured α-β fit when the tuning table holds one for this
        device's link class, else the spec-constant model whose cutoff
        IS ``TREE_CUTOFF_BYTES`` (decisions byte-identical to the
        pre-model planner).  Resolved lazily, re-resolved after every
        :meth:`refresh`/:meth:`set_spec` epoch bump."""
        with self._lock:
            if self._cost_model is None:
                self._cost_model = _resolve_cost_model()
            return self._cost_model

    def set_cost_model(self, model):
        """Inject a cost model (tests) → the previous one; ``None``
        restores lazy table resolution at the next plan."""
        with self._lock:
            prev = self._cost_model
            self._cost_model = model
            return prev

    # -- planning ----------------------------------------------------------
    def cache_size(self) -> int:
        with self._lock:
            return len(self._plans)

    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    def plan(self, payload_bytes: int, world: int, config,
             axis: str = "data", op: Optional[str] = None) -> ReductionPlan:
        """Resolve (and cache) the route for one payload class."""
        world = int(world)
        bucket = _bucket(payload_bytes)
        with self._lock:
            spec = None
            if config is not None and getattr(config, "strategy",
                                              "flat") != "flat":
                spec = self.spec()
            key = (bucket, world, config, spec, self._epoch)
            plan = self._plans.get(key)
            if plan is not None:
                return plan
            if self._cost_model is None:
                self._cost_model = _resolve_cost_model()
            strategy, reason, inner, model = _decide(
                payload_bytes, world, spec, config,
                cost_model=self._cost_model)
            plan = ReductionPlan(strategy=strategy, reason=reason,
                                 world=world, inner=inner,
                                 payload_bucket=bucket, config=config)
            self._plans[key] = plan
            self._c_plans.inc(1, strategy=strategy, reason=reason,
                              model=model)
        try:
            flight_record("plan_decide", strategy=strategy, reason=reason,
                          world=world, inner=inner,
                          payload_bucket=bucket, op=op, model=model,
                          codec=(config.compression if config is not None
                                 else "none"))
        except Exception:
            pass
        return plan

    def resolved_routing(self, config,
                         world: Optional[int] = None) -> str:
        """'flat' when every plan under this config is the flat
        dispatch — no config, ``strategy='flat'``, ``'auto'`` with no
        trusted topology (the default everywhere topology is unknown),
        or an EXPLICIT strategy whose structural preconditions fail so
        :func:`_decide` falls back to flat anyway ('hierarchical'
        without a trusted multi-host topology, 'tree' on a non-pow2
        world, any route at world 1) — else the config's strategy
        field.  The checkpoint guards key on THIS, so pre-planner
        checkpoints (no strategy recorded) resume freely under default
        configs, a real routing switch refuses loudly, and a stamp can
        never name a route the sync didn't run (a 'hierarchical'
        request that actually synced flat must not poison resume on a
        cluster where it WOULD route).  Pass ``world`` (the fit's mesh
        size) where known — both checkpoint guards do; without it the
        hierarchical divisibility check falls back to the spec's own
        world and the tree pow2 check is skipped (tree needs no
        topology, so there is nothing to fall back to)."""
        if config is None:
            return "flat"
        s = getattr(config, "strategy", "flat")
        if s == "flat":
            return "flat"
        if world is not None and int(world) <= 1:
            return "flat"
        if s in ("auto", "hierarchical"):
            spec = self.spec()
            if spec is None or not spec.trusted:
                return "flat"
            if s == "hierarchical":
                inner = spec.devices_per_host
                w = int(world) if world is not None else spec.world
                if not (spec.multi_host and 1 <= inner < w
                        and w % inner == 0):
                    return "flat"
        if s == "tree":
            w = int(world) if world is not None else None
            if w is not None and not _is_pow2(w):
                return "flat"
        return s


def _resolve_cost_model():
    """The planner's cost model: a measured α-β fit when the tuning
    table holds one for this device's ICI link class (honesty: the fit
    was recorded from real watched-dispatch timings on a matching
    ``device_kind``), else :meth:`CollectiveCostModel.spec` whose
    cutoff is exactly ``TREE_CUTOFF_BYTES`` — no table, byte-identical
    decisions.  Never raises (planning must not break on a torn table
    or an import cycle during teardown)."""
    try:
        from ..telemetry.autotune import (COST_MODEL_GEOMETRY,
                                          COST_MODEL_SPACE,
                                          CollectiveCostModel)
        from ..telemetry.tunetable import get_tuneplane

        def _gate(w):
            a, b = w.get("alpha_s"), w.get("beta_s_per_byte")

            def num(v):
                return (isinstance(v, (int, float))
                        and not isinstance(v, bool)
                        and math.isfinite(v))

            return num(a) and num(b) and a >= 0.0 and b > 0.0

        won = get_tuneplane().consult(
            "CollectivePlanner", COST_MODEL_SPACE, COST_MODEL_GEOMETRY,
            validate=_gate)
        if won is not None:
            return CollectiveCostModel(
                alpha_s=float(won["alpha_s"]),
                beta_s_per_byte=float(won["beta_s_per_byte"]),
                source="fitted")
        return CollectiveCostModel.spec(TREE_CUTOFF_BYTES)
    except Exception:
        return None


def _decide(payload_bytes: int, world: int,
            spec: Optional[TopologySpec], config, cost_model=None):
    """The decision table → ``(strategy, reason, inner, model)``.

    Structural rules over payload bytes × world size × link class.
    The ONE numeric threshold — the 'auto' tree-vs-ring payload
    crossover — routes through ``cost_model.tree_cutoff_bytes(world)``:
    a measured α-β fit when the tuning table holds one (``model=
    'fitted'``), else the spec-constant model whose cutoff is the
    hardcoded ``TREE_CUTOFF_BYTES`` (``model='spec'``, decisions
    byte-identical to the pre-model planner).  Paths that consult no
    cost model at all — forced strategies, single rank, unknown
    topology — label ``model='fallback'``: unknown topology still
    plans flat and nothing is ever priced from fabricated numbers."""
    requested = getattr(config, "strategy", "flat") if config is not None \
        else "flat"
    if requested == "flat":
        return "flat", "forced", world, "fallback"
    if world <= 1:
        return "flat", "single_rank", world, "fallback"
    known = spec is not None and spec.trusted
    inner = spec.devices_per_host if known else world
    hier_ok = (known and spec.multi_host and 1 <= inner < world
               and world % inner == 0)
    if requested == "ring":
        return "ring", "forced", world, "fallback"
    if requested == "tree":
        if _is_pow2(world):
            return "tree", "forced", world, "fallback"
        return "flat", "non_pow2_world", world, "fallback"
    if requested == "hierarchical":
        if hier_ok:
            return "hierarchical", "forced", inner, "fallback"
        return "flat", ("no_topology" if not known
                        else "indivisible_world"), world, "fallback"
    if requested != "auto":
        raise ValueError(f"strategy={requested!r}: must be one of "
                         f"{STRATEGIES}")
    # -- auto --------------------------------------------------------------
    if not known:
        return "flat", "unknown_topology", world, "fallback"
    cutoff, mlabel = TREE_CUTOFF_BYTES, "spec"
    if cost_model is not None:
        try:
            cutoff = cost_model.tree_cutoff_bytes(world)
            mlabel = cost_model.source
        except Exception:
            cutoff, mlabel = TREE_CUTOFF_BYTES, "spec"
    if payload_bytes <= cutoff:
        if _is_pow2(world):
            return "tree", "latency_bound", world, mlabel
        return "flat", "non_pow2_world", world, mlabel
    compresses_here = (config is not None and config.compresses
                       and payload_bytes >= config.min_size * 4)
    if hier_ok and compresses_here:
        return "hierarchical", "multi_host_codec", inner, mlabel
    if hier_ok:
        return "hierarchical", "multi_host", inner, mlabel
    return "ring", "bandwidth_bound", world, mlabel


_default_planner = CollectivePlanner()
_planner_lock = threading.Lock()


def get_planner() -> CollectivePlanner:
    """The process-wide planner every dispatch plans through."""
    return _default_planner


def set_planner(planner: CollectivePlanner) -> CollectivePlanner:
    """Swap the process planner (tests) → the previous one."""
    global _default_planner
    with _planner_lock:
        prev = _default_planner
        _default_planner = planner
        return prev


def planned_psum(x, axis: Optional[str], config,
                 op: str = "compressed_psum", record: bool = True):
    """The planner-routed ``psum``: resolve a :class:`ReductionPlan` for
    this payload (trace-time; shapes and the axis size are static under
    shard_map tracing) and execute it.  ``config=None`` — no policy at
    all — bypasses the planner and traces exactly as
    :func:`~synapseml_tpu.parallel.compression.compressed_psum` always
    has, as does any plan that resolves ``flat``."""
    if axis is None:
        return x
    from .compression import compressed_psum
    if config is None:
        return compressed_psum(x, axis, None, op=op, record=record)
    if getattr(config, "strategy", "flat") == "flat":
        return compressed_psum(x, axis, config, op=op, record=record)
    import numpy as np
    from jax import lax
    world = int(lax.axis_size(axis))
    nbytes = int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
    plan = get_planner().plan(nbytes, world, config, axis=str(axis), op=op)
    return plan.execute(x, axis, op=op, record=record)
