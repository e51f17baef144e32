"""Compressed + sharded collectives: quantized allreduce with error
feedback, behind the :mod:`~synapseml_tpu.parallel.collectives` dispatch.

The f32 gradient allreduce of a data-parallel fine-tune and GBDT's
per-iteration histogram psum are pure bandwidth — both move 4 bytes per
value when far fewer carry the signal.  This module implements the two
levers:

- **Quantized allreduce codecs** (EQuARX, arXiv:2506.17615): ``bf16``
  (cast, reduce in bf16, cast back — 2x wire) and ``int8`` (chunked
  symmetric quantization with one f32 scale per ``chunk`` values —
  ~3.9x wire at chunk=256).  int8 reduces as reduce-scatter +
  all-gather of QUANTIZED shards: an ``all_to_all`` ships each rank its
  shard's quantized copies, the shard sums in f32 locally, and the
  re-quantized result all-gathers back — both wire phases ride int8.
- **Error feedback** (1-bit SGD lineage): the per-leaf quantization
  error is carried in a persistent residual and added to the next
  step's gradient instead of lost, so compressed SGD tracks the f32
  trajectory (pinned in tests/test_collectives_compression.py).
- **Sharded weight update** (Xu et al., arXiv:2004.13336): gradients
  reduce-scatter, each rank updates its 1/N shard of params/moments,
  updated params all-gather back — the N-way replicated optimizer work
  disappears (see :mod:`~synapseml_tpu.models.dl.training`).

Everything here is trace-time jax: the codecs run INSIDE jit/shard_map
bodies, so the compressed collective is part of the compiled step.

Non-finite policy (chunk-granular pass-through): an int8 chunk holding
any NaN/Inf decodes to all-NaN on every rank — gradient-overflow
detection still trips, at chunk granularity instead of element
granularity.  bf16 casts non-finites through natively.

Determinism: every rank decodes the SAME gathered bytes in the SAME
order, so compressed reductions are replicated exactly like ``psum`` —
the property GBDT's identical-tree-on-every-rank growth relies on.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..telemetry import get_registry
from .mesh import DATA_AXIS

#: codecs understood by :class:`CollectiveConfig.compression`
CODECS = ("none", "bf16", "int8")


@dataclasses.dataclass(frozen=True)
class CollectiveConfig:
    """Per-estimator collective compression/sharding policy.

    Frozen + hashable on purpose: it joins jit/lru static keys (the
    GBDT ``_make_step`` cache, the grower jit signatures), so two fits
    with different codecs compile distinct programs.
    """
    #: "none" | "bf16" | "int8" — wire codec for eligible reductions
    compression: str = "none"
    #: reduce-scatter gradients, update the local 1/N shard, all-gather
    #: params back (DL train path only; GBDT histograms have no
    #: optimizer state to shard)
    sharded_update: bool = False
    #: carry quantization error into the next step's gradient
    #: (DL gradient sync only — GBDT histograms are re-derived per
    #: split, so there is no stream to feed an error into)
    error_feedback: bool = False
    #: leaves with fewer elements stay f32 (compression overhead beats
    #: the wire win on tiny tensors; biases/scalars also carry
    #: outsized signal per byte)
    min_size: int = 2048
    #: values sharing one f32 scale in the int8 codec
    chunk: int = 256
    #: force the manual data-parallel shard_map step even with
    #: ``compression='none'`` — a measurement pin, not a perf knob: a
    #: compressed-vs-f32 pair where the f32 leg rides pjit would
    #: conflate the codec with the execution-mode change, so such a
    #: pair pins BOTH legs to the manual mode
    manual: bool = False
    #: reduction ROUTE (:mod:`~synapseml_tpu.parallel.planner`):
    #: 'auto' (default — per-payload planner choice; resolves 'flat'
    #: wherever the topology is unknown, so defaults trace byte-
    #: identically to the pre-planner dispatch) | 'flat' (whatever
    #: jax.lax emits — today's path, pinned) | 'ring' | 'tree' |
    #: 'hierarchical' (intra-host f32, inter-host through the codec).
    #: A non-auto routing strategy also engages the manual dispatch
    #: paths (the route must be ours to schedule).
    strategy: str = "auto"

    def __post_init__(self):
        if self.compression not in CODECS:
            raise ValueError(
                f"compression={self.compression!r}: must be one of {CODECS}")
        if self.chunk < 8:
            raise ValueError(f"chunk={self.chunk}: must be >= 8")
        from .planner import STRATEGIES
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"strategy={self.strategy!r}: must be one of {STRATEGIES}")

    @property
    def enabled(self) -> bool:
        return (self.compression != "none" or self.sharded_update
                or self.manual or self.routes)

    @property
    def routes(self) -> bool:
        """An EXPLICIT routing request ('auto' alone does not enable a
        config — on unknown topology it is indistinguishable from flat,
        and on known topology it engages wherever the codec/manual
        knobs already put dispatch in our hands)."""
        return self.strategy in ("ring", "tree", "hierarchical")

    @property
    def compresses(self) -> bool:
        return self.compression != "none"


def resolve_collective_config(value: Any) -> Optional[CollectiveConfig]:
    """The one parser for estimator-level ``collectiveCompression``
    params and ``BoostingConfig.collective_compression``: accepts
    ``None``/``"none"`` (off), a codec shorthand (``"bf16"``/``"int8"``
    — error feedback ON, the right default for gradient streams; GBDT
    ignores the flag), a full :class:`CollectiveConfig`, or its
    ``dataclasses.asdict`` form (checkpointed configs)."""
    if value is None:
        return None
    if isinstance(value, CollectiveConfig):
        return value if value.enabled else None
    if isinstance(value, dict):
        # a checkpointed BoostingConfig round-trips a CollectiveConfig
        # through dataclasses.asdict — rebuild it (unknown keys from a
        # newer build are dropped, matching Booster.from_dict's policy)
        fields = {f.name for f in dataclasses.fields(CollectiveConfig)}
        return resolve_collective_config(CollectiveConfig(
            **{k: v for k, v in value.items() if k in fields}))
    if isinstance(value, str):
        if value == "none" or value == "":
            return None
        if value not in CODECS:
            raise ValueError(
                f"collectiveCompression={value!r}: must be one of {CODECS} "
                "or a CollectiveConfig")
        cfg = CollectiveConfig(compression=value, error_feedback=True)
        if value == "int8":
            tuned = _tuned_int8_chunk()
            if tuned is not None:
                cfg = dataclasses.replace(cfg, chunk=tuned)
        return cfg
    raise TypeError(
        f"collectiveCompression accepts a str codec or CollectiveConfig, "
        f"got {type(value).__name__}")


def _tuned_int8_chunk() -> Optional[int]:
    """The ``int8_chunk`` tuning-table winner for this device, or None
    (keep the 256 default).  Only the codec SHORTHAND consults the
    table: an explicit ``CollectiveConfig`` (or its checkpointed dict
    form) is the caller's decision and passes through untouched."""
    try:
        from ..telemetry.tunetable import geometry_key, get_tuneplane
        winner = get_tuneplane().consult(
            "resolve_collective_config", "int8_chunk",
            geometry_key(numel=1 << 18),
            validate=lambda w: (isinstance(w.get("chunk"), int)
                                and not isinstance(w["chunk"], bool)
                                and w["chunk"] >= 8))
    except Exception:
        return None
    return int(winner["chunk"]) if winner is not None else None


def stream_eligible(shape, dtype,
                    config: Optional[CollectiveConfig]) -> bool:
    """The size/dtype half of the eligibility predicate: does a payload
    of this shape/dtype belong to the big flat stream at all (large
    float, ``min_size`` or more elements) — before asking whether the
    codec engages on it?  The routing-only stream
    (:func:`compressed_tree_sync` under an explicit strategy with
    ``compression='none'``) partitions leaves by THIS, so the big/small
    split can never disagree between compressing and routing-only
    configs."""
    return (config is not None
            and int(np.prod(shape)) >= config.min_size
            and jnp.issubdtype(dtype, jnp.floating))


def codec_eligible(shape, dtype, config: Optional[CollectiveConfig]) -> bool:
    """THE eligibility predicate — does the codec engage for a payload of
    this shape/dtype under ``config``?  One implementation on purpose:
    the traced reductions (:func:`compressed_psum`,
    :func:`compressed_tree_sync`), the wire accounting
    (:func:`wire_nbytes`), and the host-side codec labels
    (``collectives.allreduce_fn``) must all agree, or metrics report
    int8 wire for ops that really reduced in f32."""
    return (config is not None and config.compresses
            and stream_eligible(shape, dtype, config))


# -- wire accounting ---------------------------------------------------------

def logical_nbytes(x) -> int:
    """Bytes the values occupy at their LOGICAL dtype (what an
    uncompressed collective would move per shard)."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(x):
        size, dtype = getattr(leaf, "size", None), getattr(leaf, "dtype",
                                                           None)
        if size is not None and dtype is not None:
            n += int(size) * np.dtype(dtype).itemsize
    return n


def wire_nbytes(x, config: Optional[CollectiveConfig],
                channel_major: bool = False) -> int:
    """Bytes the codec actually puts on the wire for ``x``: bf16 halves
    every eligible f32; int8 ships 1 byte/value + one f32 scale per
    ``chunk`` — INCLUDING the zero-pad values the layout adds (with
    ``channel_major``, each trailing channel pads to a chunk multiple —
    the :func:`compressed_psum` layout; the flat int8 stream then rounds
    up to a whole chunk).  The final pad to an ``n_ranks * chunk``
    multiple depends on the axis size, which this accounting cannot see;
    the ≤ ``(n-1) * chunk`` values it omits are noise against real
    payloads.  ``config=None``/"none" → logical bytes."""
    if config is None or not config.compresses:
        return logical_nbytes(x)
    total = 0
    int8_vals = 0
    for leaf in jax.tree_util.tree_leaves(x):
        size, dtype = getattr(leaf, "size", None), getattr(leaf, "dtype",
                                                           None)
        if size is None or dtype is None:
            continue
        size = int(size)
        shape = tuple(getattr(leaf, "shape", ()))
        if not codec_eligible((size,), dtype, config):
            total += size * np.dtype(dtype).itemsize
        elif config.compression == "bf16":
            total += size * 2
        elif channel_major and len(shape) >= 2:
            C = shape[-1]
            per = size // C
            int8_vals += C * (-(-per // config.chunk) * config.chunk)
        else:
            int8_vals += size
    if int8_vals:
        int8_vals = -(-int8_vals // config.chunk) * config.chunk
        total += int8_vals + (int8_vals // config.chunk) * 4
    return total


def record_compressed(op: str, axis, x,
                      config: Optional[CollectiveConfig],
                      channel_major: bool = False,
                      strategy: str = "flat",
                      codec: Optional[str] = None,
                      wire: Optional[int] = None) -> None:
    """Trace-time wire/logical accounting for a compressed collective —
    the codec-aware counterpart of ``collectives._record`` (which
    assumed logical dtype size for every op and would double-count and
    mis-rank codecs).  ``strategy`` is the planner route the bytes take
    (ISSUE 14: every strategy choice attributable), 'flat' for the
    direct dispatch.  ``codec``/``wire`` override the config-derived
    label and byte model for routed dispatches whose wire differs from
    the flat one (a tree that demoted int8 ships f32; hierarchical adds
    intra-host f32 legs — see ``ReductionPlan.wire_nbytes``).
    Telemetry must never break a trace."""
    try:
        if codec is None:
            codec = config.compression if config is not None else "none"
        logical = logical_nbytes(x)
        if wire is None:
            wire = wire_nbytes(x, config, channel_major=channel_major)
        reg = get_registry()
        labels = dict(op=op, axis=str(axis), codec=codec, strategy=strategy)
        reg.counter(
            "collective_wire_bytes_total",
            "per-shard bytes collectives actually put on the wire, by "
            "op, mesh axis, codec and routing strategy",
            ("op", "axis", "codec", "strategy")).inc(wire, **labels)
        reg.gauge(
            "collective_compression_ratio",
            "logical / wire bytes of the last traced collective, by op, "
            "mesh axis, codec and routing strategy",
            ("op", "axis", "codec", "strategy")).set(
                (logical / wire) if wire else 1.0, **labels)
    except Exception:
        pass


# -- codecs ------------------------------------------------------------------

def bf16_encode(x):
    return x.astype(jnp.bfloat16)


def bf16_decode(q):
    return q.astype(jnp.float32)


def int8_encode(flat, chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked symmetric int8 quantization of a flat f32 vector whose
    length is a (static) multiple of ``chunk``.

    → ``(q int8 (n_chunks, chunk), scales f32 (n_chunks,))`` with
    ``scale = max|finite x| / 127`` per chunk.  A chunk containing any
    non-finite value gets a NaN scale, so the whole chunk decodes to
    NaN — the documented pass-through policy (overflow detection trips
    at chunk granularity)."""
    xc = flat.reshape(-1, chunk)
    finite = jnp.isfinite(xc)
    amax = jnp.max(jnp.where(finite, jnp.abs(xc), 0.0), axis=1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xc / safe[:, None]), -127, 127).astype(jnp.int8)
    scale = jnp.where(jnp.all(finite, axis=1), scale, jnp.nan)
    return q, scale.astype(jnp.float32)


def int8_decode(q, scales) -> jnp.ndarray:
    """Inverse of :func:`int8_encode` → flat f32 (NaN-scale chunks decode
    to all-NaN)."""
    return (q.astype(jnp.float32) * scales[:, None]).reshape(-1)


@functools.partial(jax.jit, static_argnames=("chunk",))
def int8_roundtrip_jit(flat: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """Jitted encode→decode round trip of a flat f32 vector — the int8
    codec's standalone entry point: the ``int8_chunk`` autotune space
    times it per candidate chunk, and it is registered with the warmup
    lattice (``REGISTERED_ENTRY_POINTS``) like every other tunable
    program.  The in-collective codec runs inside larger jitted bodies;
    this isolates the quantization cost itself."""
    return int8_decode(*int8_encode(flat, chunk))


def _channel_major_padded(x, chunk: int):
    """Channel-major flatten with each channel zero-padded to a
    ``chunk`` multiple → ``(flat, per, per_padded)``.

    Histogram-style arrays carry heterogeneous channels on the LAST
    axis (grad/hess/count for GBDT — counts are ~1e3x gradients); a
    C-order flatten would interleave them into shared int8 chunks and
    the small channel would quantize to zero.  Moving the channel axis
    leading is not enough on its own: a channel whose element count is
    not a chunk multiple (28 features x 64 bins = 1792, say) leaves a
    BOUNDARY chunk spanning two channels, where the big channel's amax
    scale flattens the small one.  Padding every channel to a chunk
    multiple keeps each chunk strictly single-channel.  Pure layout —
    inverted exactly by :func:`_channel_major_padded_inv`."""
    if getattr(x, "ndim", 0) >= 2:
        C = x.shape[-1]
        moved = jnp.moveaxis(x, -1, 0).reshape(C, -1)
        per = moved.shape[1]
        per_p = -(-per // chunk) * chunk
        if per_p != per:
            moved = jnp.pad(moved, ((0, 0), (0, per_p - per)))
        return moved.reshape(-1), per, per_p
    return x.reshape(-1), None, None


def _channel_major_padded_inv(flat, shape, per, per_p):
    if len(shape) >= 2:
        C = shape[-1]
        out = flat.reshape(C, per_p)[:, :per]
        return jnp.moveaxis(out.reshape((C,) + tuple(shape[:-1])), 0, -1)
    return flat.reshape(shape)


def _pad_to(flat, unit: int):
    """Zero-pad a flat vector to a multiple of ``unit`` (static)."""
    n = flat.shape[0]
    padded = -(-n // unit) * unit
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat


def int8_reduce_scatter(flat, axis: str, chunk: int) -> jnp.ndarray:
    """Quantized reduce-scatter of a flat f32 vector whose length is a
    (static) multiple of ``n_ranks * chunk``: each rank quantizes its
    full vector per-chunk, an ``all_to_all`` ships shard ``r``'s
    quantized copies to rank ``r``, and the shard sums in f32 locally.

    → this rank's f32 shard of the SUM (length ``len / n``).  The wire
    carries int8 + per-chunk f32 scales — the reduce-scatter phase of
    the EQuARX-style quantized allreduce, and directly the gradient
    half of the sharded weight update."""
    n = lax.axis_size(axis)
    if n == 1:
        # single rank: same quantize→dequantize the wire would apply,
        # so 1-device runs surface the identical numeric policy the
        # gang sees (and the error-feedback tests exercise it locally)
        q, s = int8_encode(flat, chunk)
        return int8_decode(q, s)
    shard = flat.shape[0] // n
    q, s = int8_encode(flat, chunk)                   # (C, chunk), (C,)
    q = q.reshape(n, shard // chunk, chunk)
    s = s.reshape(n, shard // chunk)
    q_x = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=False)
    s_x = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=False)
    # decode each peer's copy of MY shard and sum in f32 (fixed 0..n-1
    # order → replicated-deterministic result after the gather below)
    vals = q_x.astype(jnp.float32) * s_x[..., None]   # (n, shard/chunk, chunk)
    return jnp.sum(vals, axis=0).reshape(-1)


def int8_all_gather(shard, axis: str, chunk: int) -> jnp.ndarray:
    """Quantized all-gather of equal f32 shards (length a static
    multiple of ``chunk``) → the concatenated f32 vector, identical on
    every rank.  The all-gather phase of the quantized allreduce."""
    n = lax.axis_size(axis)
    q, s = int8_encode(shard, chunk)
    if n == 1:
        return int8_decode(q, s)
    qg = lax.all_gather(q, axis_name=axis)            # (n, C, chunk)
    sg = lax.all_gather(s, axis_name=axis)            # (n, C)
    return (qg.astype(jnp.float32) * sg[..., None]).reshape(-1)


# -- in-jit compressed reductions -------------------------------------------

def compressed_psum(x, axis: Optional[str],
                    config: Optional[CollectiveConfig],
                    op: str = "compressed_psum", record: bool = True):
    """Drop-in ``psum`` with the config's codec on the wire.

    The GBDT histogram-allreduce replacement: stateless (no error
    feedback — each node's histogram is an independent quantity, not a
    stream), sum semantics, identical result on every rank.  Arrays
    with a trailing channel axis are re-laid out channel-major before
    chunking (see :func:`_channel_major_padded`).  Falls back to plain
    ``lax.psum`` for ``config=None``/"none"/too-small payloads, so the
    default path traces byte-identically to today's."""
    if axis is None:
        return x
    if not codec_eligible(x.shape, x.dtype, config):
        # record under the CALLER's op (not the psum wrapper's): a
        # too-small/non-float payload of the same logical collective
        # must not split into a different metric series — and with
        # record=False the caller accounts the op itself (allreduce_fn's
        # host wrapper), so recording here would double-count
        if record:
            from .collectives import _record
            _record(op, axis, x)
        return lax.psum(x, axis_name=axis)
    if record:
        # record=False for callers that already account the op at their
        # own level (allreduce_fn's host wrapper) — one op, one series
        from .collectives import _record
        _record(op, axis, x, config=config, channel_major=True)
    shape = x.shape
    orig_dtype = x.dtype
    if config.compression == "bf16":
        out = lax.psum(bf16_encode(x), axis_name=axis)
        return bf16_decode(out).astype(orig_dtype)
    flat, per, per_p = _channel_major_padded(x.astype(jnp.float32),
                                             config.chunk)
    size = flat.shape[0]
    # axis size is static inside shard_map tracing (it comes from the
    # mesh), so the padding below stays shape-static
    n = lax.axis_size(axis)
    flat = _pad_to(flat, int(n) * config.chunk)
    shard = int8_reduce_scatter(flat, axis, config.chunk)
    total = int8_all_gather(shard, axis, config.chunk)
    return _channel_major_padded_inv(total[:size], shape, per,
                                     per_p).astype(orig_dtype)


def flatten_with_residuals(leaves, big, res_leaves, padded: int):
    """Concatenate the ``big`` leaves (f32, plus their error-feedback
    residuals when carried) into one zero-padded flat stream of length
    ``padded`` — the ONE pack step shared by
    :func:`compressed_tree_sync` and the DL sharded weight update (the
    EF recursion lives here once; a hardening applied to one path
    cannot silently miss the other)."""
    eff = []
    for i in big:
        g = leaves[i].astype(jnp.float32)
        if res_leaves is not None:
            g = g + res_leaves[i].reshape(g.shape)
        eff.append(g.reshape(-1))
    flat = jnp.concatenate(eff) if eff else jnp.zeros((0,), jnp.float32)
    return jnp.pad(flat, (0, padded - flat.shape[0]))


def unpack_residuals(err, big, leaves, res_leaves):
    """Scatter the flat quantization error back into the per-rank
    residual leaves (``e' = (g+e) - Q(g+e)``) — the inverse of
    :func:`flatten_with_residuals`' packing order."""
    new_res = list(res_leaves)
    offset = 0
    for i in big:
        sz = leaves[i].size
        new_res[i] = err[offset:offset + sz].reshape(new_res[i].shape)
        offset += sz
    return new_res


# -- world-size-independent re-sharding (elastic gang resize) ---------------
#
# Two pieces of training state are laid out by WORLD SIZE: the per-rank
# error-feedback residuals (stacked ``(n, *leaf.shape)``) and the
# sharded-update flat moment stream (padded to an ``n * unit`` multiple).
# An N-rank checkpoint restoring on M ranks goes through a CANONICAL
# (world-size-free) form first — gather-to-canonical-then-reshard — so
# the restored state is a pure function of the checkpoint, identical
# whichever size reads it.

def canonical_residuals(stacked):
    """Stacked per-rank EF residuals ``(n, *shape)`` → the canonical
    ``(*shape,)`` TOTAL carried error.

    The EF recursion is additive in SUM units: each rank transmits
    ``Q(g_r + e_r)`` and keeps ``e_r' = (g_r + e_r) - Q(g_r + e_r)``, so
    the quantity the compressed stream owes the true gradient trajectory
    is ``sum_r e_r`` — the per-rank decomposition is an artifact of who
    computed what, not training state.  Summation order is the stacked
    rank order (0..n-1), deterministic on every reader."""
    return np.asarray(stacked, dtype=np.float32).sum(axis=0)


def reshard_residuals(canonical, n: int):
    """Canonical total error ``(*shape,)`` → ``(n, *shape)`` stacked
    per-rank residuals: rank 0 carries the whole total, ranks 1.. carry
    zeros.  Exact (no divide — splitting ``e / n`` would round) and
    preserves the EF invariant ``sum_r e_r == canonical``; the
    decomposition re-balances itself within one step (each rank's next
    error is its own quantization error)."""
    canonical = np.asarray(canonical, dtype=np.float32)
    out = np.zeros((int(n),) + canonical.shape, dtype=np.float32)
    out[0] = canonical
    return out


def reshard_flat_stream(buf, total: int, new_padded: int):
    """A flat padded per-stream vector (sharded-update moments) laid out
    for one world size → the same stream re-padded for another: trim to
    the ``total`` real values (pad positions hold zeros — pad gradients
    are structurally zero, so their moments never grow), re-pad to
    ``new_padded``."""
    buf = np.asarray(buf)
    if total > buf.shape[0] or new_padded < total:
        raise ValueError(
            f"cannot re-lay stream of {buf.shape[0]} values to "
            f"{new_padded} keeping {total} real values")
    out = np.zeros((int(new_padded),), dtype=buf.dtype)
    out[:total] = buf[:total]
    return out


def compressed_tree_sync(tree, axis: Optional[str],
                         config: CollectiveConfig,
                         residuals=None, mean: bool = True,
                         op: str = "grad_sync"):
    """Gradient-tree allreduce with compression + per-leaf error
    feedback: → ``(reduced_tree, new_residuals)``.

    Large float leaves concatenate into one flat buffer (the
    ``tree_psum_bucketed`` fusion idea, applied to the compressed
    stream), ride the quantized reduce-scatter + all-gather, and unpack;
    small/non-float leaves ride a plain bucketed psum.  With
    ``residuals`` (a pytree matching ``tree``, each leaf stacked
    ``(1, *leaf.shape)`` per-rank under shard_map), each rank transmits
    ``Q(g + e)`` and keeps ``e' = (g + e) - Q(g + e)`` — the classic
    error-feedback recursion, in SUM units (the mean divide applies to
    the reduced total only).
    """
    from .collectives import tree_psum_bucketed, _record
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if axis is not None:
        n = lax.axis_size(axis)
    else:
        n = 1
    # the big-leaf stream: codec-eligible leaves, plus — for a
    # routing-only config (strategy set, compression 'none') — the same
    # large-float class routed at f32, so an explicit ring/tree/
    # hierarchical request still schedules the gradient stream
    big = [i for i, lf in enumerate(leaves)
           if stream_eligible(lf.shape, lf.dtype, config)
           and (config.compresses or config.routes)]
    small = [i for i in range(len(leaves)) if i not in big]

    out = list(leaves)
    new_res = None
    if residuals is not None:
        new_res = list(jax.tree_util.tree_leaves(residuals))
    if small and axis is not None:
        small_tree = [leaves[i] for i in small]
        summed = tree_psum_bucketed(small_tree, axis=axis)
        for j, i in enumerate(small):
            out[i] = summed[j] / n if mean else summed[j]
    if big:
        # the planner resolves the gradient stream's route at trace
        # time (flat everywhere topology is unknown — the pre-planner
        # jaxpr, byte-identical); non-flat routes reduce through
        # ReductionPlan.reduce_flat with the SAME per-leaf EF contract
        plan = None
        if axis is not None and getattr(config, "strategy",
                                        "flat") != "flat":
            from .planner import get_planner
            size_est = int(sum(leaves[i].size for i in big)) * 4
            plan = get_planner().plan(size_est, int(n), config,
                                      axis=str(axis), op=op)
        routed = plan is not None and plan.strategy != "flat"
        size = int(sum(leaves[i].size for i in big))
        big_leaves = [leaves[i] for i in big]
        codec = (plan.wire_codec((size,), jnp.float32) if routed
                 else None)
        if routed:
            # calls/logical series, then the strategy-labeled wire
            # series at the codec and bytes the resolved route REALLY
            # ships (a tree route demotes int8 to the f32 wire;
            # hierarchical counts its intra-host f32 legs plus the
            # 1/inner codec shard) — flat-model accounting here would
            # claim int8 wire for a route that ships f32
            _record(op, axis, big_leaves)
            record_compressed(op, axis, big_leaves,
                              config if codec != "none" else None,
                              strategy=plan.strategy, codec=codec,
                              wire=plan.wire_nbytes(big_leaves, codec))
        else:
            _record(op, axis, big_leaves, config=config, strategy="flat")
        flat = flatten_with_residuals(leaves, big, new_res, size)
        want_err = new_res is not None and config.error_feedback
        if routed:
            flat_p = _pad_to(flat, plan.pad_unit(codec))
            total_p, err_p = plan.reduce_flat(flat_p, axis, codec,
                                              want_err=want_err)
            total = total_p[:size]
            if want_err:
                new_res = unpack_residuals(err_p[:size], big, leaves,
                                           new_res)
        elif not config.compresses:
            # a routing-only stream whose plan resolved flat (unknown
            # topology / structural fallback): plain f32 psum — the
            # same wire the small-leaf path rides
            total = (lax.psum(flat, axis_name=axis)
                     if axis is not None else flat)
        elif config.compression == "bf16":
            sent = bf16_decode(bf16_encode(flat))
            if axis is not None:
                total = bf16_decode(lax.psum(bf16_encode(flat),
                                             axis_name=axis))
            else:
                total = sent
            if want_err:
                new_res = unpack_residuals(flat - sent[:size], big,
                                           leaves, new_res)
        else:
            flat_p = _pad_to(flat, int(n) * config.chunk)
            q, s = int8_encode(flat_p, config.chunk)
            sent = int8_decode(q, s)[:size]
            if axis is not None and int(n) > 1:
                shard = int8_reduce_scatter(flat_p, axis, config.chunk)
                total = int8_all_gather(shard, axis, config.chunk)[:size]
            else:
                total = sent
            if want_err:
                new_res = unpack_residuals(flat - sent[:size], big,
                                           leaves, new_res)
        offset = 0
        for i in big:
            sz = leaves[i].size
            shp = leaves[i].shape
            red = total[offset:offset + sz].reshape(shp)
            out[i] = (red / n if mean else red).astype(leaves[i].dtype)
            offset += sz
    # no big leaves (config doesn't compress, or nothing eligible):
    # the small-leaf branch above already rode the whole tree through
    # the plain bucketed psum — the f32 wire, one traced reduce
    reduced = jax.tree_util.tree_unflatten(treedef, out)
    if residuals is not None:
        new_res = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(residuals), new_res)
    return reduced, new_res
