"""Slotted cache + continuous-batching decode engine.

The Orca-style in-flight batching / vLLM-style paged-KV pattern (Yu et
al., OSDI'22; Kwon et al., SOSP'23) adapted to XLA's static-shape world:
instead of dynamically-sized pages, the cache is a FIXED tree of
``n_slots`` independent rows per layer and one jitted decode step
advances every ACTIVE slot by one token.  Admission and eviction happen
between steps on the host, so the scheduler serves heterogeneous
sequence lengths with exactly three compiled programs: one decode step,
one prefill per prompt-length bucket, and one prefix copy.

**Two kinds of state.**  What a layer keeps for a slot is what its mixer
kind declares (``model.MIXERS[kind].cache_entry``):

- a *full-attention* layer keeps rows of keys and values by token
  position, ``(n_slots, max_len, kv_heads, d_head)``.  A position can be
  sliced, copied, overwritten and rolled back, and everything below that
  reuses or moves a slot's history works on such rows.  A layer kind that
  ``LlamaConfig.attention_kinds`` describes (its own K/V heads, a key wider
  than the value) keeps the same rows PACKED, ``(n_slots, rows * kv_heads /
  f, f * width)`` with ``f`` heads side by side (``model.kv_pack``): the
  layout the paged kernel reads, at no lane of padding;
- a *sliding-attention* layer keeps such rows too where ``max_len`` is
  under two rings (``SlidingAttention.cache_rows``: a rule on sizes), so
  that a slot stays a prefix-reuse source at every length; its window acts
  in the masks and in the tiles the paged kernel walks, and the byte ledger
  counts ``min(span, window)``.  From two rings on it keeps a RING of
  ``model.ring_rows(window)`` rows a slot, position ``p`` in row ``p mod
  rows``, whatever ``max_len`` (``engine.ring``; **On a ring** below);
- a *linear-attention* layer keeps a recurrent state and a convolution
  window of a fixed size whatever the length
  (``{"state": (n_slots, heads/pack, d_k, pack*d_v) f32, "conv":
  (n_slots, taps-1, channels)}``).  It holds every token the slot ever
  took and none of them separately: it can be kept or zeroed, not sliced.
  So for a model with such layers (``engine.recurrent``) the engine takes
  no token into a state that is not the slot's own next one: padded
  positions of a prefill bucket and inactive slots of a decode step leave
  state and window exactly as they were, a prefill from position 0 starts
  from zeros, and the paths that slice or roll back by position say so:
  prefix reuse is skipped and counted
  (``llm_prefix_reuse_skipped_total``), :meth:`SlotEngine.resume`
  cold-prefills, and a drafter, a ``kv_arena`` or a
  :class:`~synapseml_tpu.serving.disagg.PrefillWorker` over the engine is
  an error at construction (``docs/api/serving.md``, "Models with
  recurrent layers").

Mechanics:

- **decode step** — the per-sequence vector ``cache_index`` path of
  :class:`~synapseml_tpu.models.llm.model.CausalAttention` writes each
  slot's K/V at its own offset, the causal mask (``key_pos <= qpos``)
  confines each slot to its own prefix, and ``slot_mask`` gates writes
  so inactive slots' rows stay untouched (they are live prefix-cache
  material; a recurrent state is gated the same way).
  ``attention_backend`` selects the attention READ: dense
  (full ``max_len`` rows, masked) or the Pallas paged kernel
  (:mod:`~synapseml_tpu.models.llm.pallas_attn` — only each slot's
  live span, one compiled step for every span; ``'auto'`` = paged on
  TPU when the geometry fits VMEM), and with it how a recurrent layer runs (the kernels of
  :mod:`~synapseml_tpu.models.llm.pallas_gdn` where attention is paged,
  a ``lax.scan`` where it is dense).
- **prefill-into-slot** — the prompt is padded to a bucket of
  :func:`prefill_buckets` (powers of two and one bucket in the top
  octave: a bounded compile count), its K/V lands in ONE slot row (sliced
  out, filled batch-1, written back), and the true-last-token logits come
  back for the first sampled token.  ``start > 0`` resumes a prefill
  after a prefix copy.
- **prefix reuse** — every slot's context (prompt plus generated
  tokens, active or retired) lies in a radix tree of token ids, one per
  tenant (:class:`~synapseml_tpu.models.llm.kvtier.RadixPrefixIndex`);
  on admit one walk finds the slot with the TRUE longest common prefix
  (tokens are compared, not hashes), the engine copies that K/V span
  into the new slot, or leaves it where the slot is its own source, and
  prefills only the tail.  Reuse is capped at ``len(prompt) - 1`` so
  the prefill always produces next-token logits.
- **retirement** — EOS or the per-request token budget frees the slot;
  its K/V and token buffer persist as prefix-cache until the slot is
  reclaimed (least-recently-retired first).
- **speculative decoding** (``spec_draft_len > 0``, greedy only) —
  before each step the per-slot :class:`~synapseml_tpu.models.llm
  .drafter.NgramDrafter` proposes a continuation span from the slot's
  own prompt+generated ids (zero model calls); any hit upgrades the
  step to a multi-token VERIFY: one jitted forward scores all S
  positions, the longest exact-greedy draft prefix plus the model's
  bonus token commit, and every slot advances by its own accepted
  span.  Rejected positions' K/V lands beyond the committed length —
  the junk-write invariant below already covers it.  Output stays
  token-exact greedy: a draft token is committed ONLY when it equals
  the model's argmax.

- **one step in flight** — an engine without a drafter hands the
  device step N+1 before it reads step N's tokens: N+1 takes its input
  tokens from N's output array, which never leaves the device, and its
  lengths and active mask from host state that does not depend on
  those tokens (a length grows by one a step; a slot that reaches its
  budget at N retires there).  The host reads, commits and returns N
  while the device runs N+1.  ``active``/``lengths``/``ctx``/
  ``kv_len`` always mean the COMMITTED state — what the returned events
  have made true; the dispatched-but-unread step is private
  (:class:`_Flight`).  EOS is known a step late: the slot rides N+1
  once more, its output is dropped and its K/V write lands at the EOS
  token's own position (junk-write invariant below); a recurrent state
  takes that junk token, which is why nothing ever reads a retired
  slot's state: the next prefill starts it from zeros.  A slot cancelled,
  preempted or handed to another request while a step is in flight has
  that step's output dropped (an admission epoch per slot).  A drafter
  needs the host's tokens BEFORE a step, so a speculative engine
  dispatches and reads each step in one call.

**On a ring.**  A window layer's ring holds the last ``rows`` positions
its slot wrote and nothing older, and it has no room past a slot's length
where junk could land unread.  So every path that assumed rows by position
says what it does there, as the recurrent state's paths do: a decode step
writes at ``position mod rows`` and the paged kernel walks the window's
position tiles through the ring; a prefill writes only its real rows (no
bucket's padding), the last ``rows`` of them, and attends over the ring's
rows before its start and its own (``model._ring_pass``), so a tail after
a reused prefix reads the window's rows before it from the ring; a prefix
reuse, in place or by ``_copy_prefix_jit`` (which copies a ring whole), is
served only while the source's ring still holds the ``sliding_window`` rows
before the prefix's end (the source has written at most ``rows - window -
2`` positions past it), else skipped and counted
(``llm_prefix_reuse_skipped_total{reason="ring_overwritten"}``,
``AdmitResult.path`` ``cold_ring``) and never served from overwritten
rows; :meth:`SlotEngine.resume` goes by the same rule and otherwise
cold-prefills; a drafter (a verify span's rejected rows would overwrite
the rows a window behind them), a ``kv_arena`` and a
:class:`~synapseml_tpu.serving.disagg.PrefillWorker` (both slice a slot's
rows ``[0, span)``) are an error at construction, the last two for packed
rows as well (``engine.kv_by_position``).

**Expert layers** (a layer whose feed-forward kind is ``"experts"``,
:mod:`~synapseml_tpu.models.llm.experts`): the program holds some of a
layer's routed experts and computes their part of the result; a token that
is a bucket's padding or an inactive slot's row routes nowhere.  Each
program returns three counts with its tokens or logits (pairs computed
here, held experts touched, tiles of the grouped product computed), which
become attributes of ``engine.step`` and ``engine.admit`` and the counters
``llm_expert_pairs_total``, ``llm_experts_touched_total`` and
``llm_expert_rows_total`` (the tiles' rows, pairs and padding).

Junk-write safety: padded prefill rows and pre-copy leftovers only ever
land at positions strictly beyond a slot's current length; decode writes
position ``q`` BEFORE attending ``<= q``, so every attendable key was
written by the slot's current occupant.

Greedy decode through this engine is token-exact with the dense-cache
:func:`~synapseml_tpu.models.llm.generate.generate` path (pinned in
tier-1), so continuous batching is a pure scheduling win, not an
approximation.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...telemetry import get_registry, step_span
from ...telemetry.flight import record as _flight_record
from .drafter import NgramDrafter
from .kvtier import ChecksumError, RadixPrefixIndex, kvtier_metrics
from .generate import sample_logits
from .experts import EXPERT_COUNTS, expert_row_tile, stats_totals
from .model import (MIXERS, RING_BLOCK, LlamaModel, init_cache, kv_pack,
                    linear_conv_channels)
from .pallas_attn import (PagedGeometry, dense_read_bytes, paged_geometry,
                          paged_live_tiles, paged_read_bytes,
                          prefill_geometry, prefill_key_blocks,
                          resolve_attention_backend)
from .pallas_gdn import resolve_recurrent_backend, slot_state_bytes


def _apply(model: LlamaModel, variables: Any, *args, **kw):
    """``model.apply`` -> ``(logits, cache, expert counts)``: for a model
    with expert layers int32 ``[expert_pairs_held, experts_touched]`` of
    the pass (:func:`~synapseml_tpu.models.llm.experts.stats_totals`),
    else None.  The counts leave a program inside the array its tokens or
    logits leave in, so reading them is no transfer of its own."""
    if not model.cfg.has_experts:
        return (*model.apply(variables, *args, **kw), None)
    (logits, cache), state = model.apply(variables, *args, mutable=["stats"],
                                         **kw)
    return logits, cache, stats_totals(state["stats"])


@functools.partial(jax.jit, static_argnames=("model", "attention_backend"),
                   donate_argnums=(2,))
def _prefill_slot_jit(model: LlamaModel, variables: Any, cache: Any,
                      tokens: jnp.ndarray, plen: jnp.ndarray,
                      slot: jnp.ndarray, start: jnp.ndarray,
                      attention_backend: str = "dense"):
    """Prefill ``plen`` real tokens (``tokens`` is padded to a static
    bucket length) into row ``slot`` starting at position ``start``.
    Returns ``(new_cache, last_logits (V,) f32)`` where ``last_logits``
    is the row for the prompt's true last token; a model with expert
    layers appends its counts (:func:`_apply`) as float32.

    Padding rows of K/V are junk that is overwritten before it is read;
    a recurrent layer takes ``valid_len=plen`` and leaves its state as
    token ``plen - 1`` made it (from zeros where ``start`` is 0).
    ``attention_backend`` says how such a layer runs, and on ``paged`` or
    ``interpret`` attention is one tiled causal kernel wherever
    :func:`~synapseml_tpu.models.llm.pallas_attn.prefill_geometry` has a
    tile for the bucket (``plen`` and ``start`` reach it as scalars: a
    bucket pays for its real tokens); elsewhere the plain scores."""
    pb = tokens.shape[0]
    row = jax.tree.map(
        lambda c: lax.dynamic_slice_in_dim(c, slot, 1, axis=0), cache)
    positions = (start + jnp.arange(pb))[None, :]
    logits, row, counts = _apply(model, variables, tokens[None, :],
                                 positions=positions, cache=row,
                                 cache_index=start, valid_len=plen,
                                 logits_at=plen - 1,
                                 attention_backend=attention_backend)
    new_cache = jax.tree.map(
        lambda c, r: lax.dynamic_update_slice_in_dim(c, r, slot, axis=0),
        cache, row)
    last = logits[0, 0]
    if counts is not None:
        last = jnp.concatenate([last, counts.astype(jnp.float32)])
    return new_cache, last


@functools.partial(jax.jit, static_argnames=(
    "model", "temperature", "top_k", "top_p", "attention_backend",
    "paged_num_tiles", "paged_tile"), donate_argnums=(2,))
def _decode_step_jit(model: LlamaModel, variables: Any, cache: Any,
                     tokens: jnp.ndarray, lengths: jnp.ndarray,
                     active: jnp.ndarray, key: jnp.ndarray,
                     temperature: float, top_k: int, top_p: float,
                     attention_backend: str = "dense",
                     paged_num_tiles: Optional[int] = None,
                     paged_tile: Optional[int] = None,
                     prev_nxt: Optional[jnp.ndarray] = None,
                     feed_host: Optional[jnp.ndarray] = None):
    """One decode step for every slot: feed each slot's pending token at
    its own position (vector ``cache_index``), sample the next.  Inactive
    slots compute a throwaway row and write nothing (``slot_mask``).

    ``prev_nxt`` is the previous step's output, still on the device:
    where ``feed_host`` is false a slot's pending token is
    ``prev_nxt[slot]`` (the host has not read it yet), elsewhere
    ``tokens[slot]``.  The engine always passes both, so one program
    exists per backend.

    ``attention_backend`` (static) selects the Pallas paged-read
    attention: each slot's K/V read covers only its live span instead of
    the full ``max_len`` row (see
    :mod:`~synapseml_tpu.models.llm.pallas_attn`).  ``paged_num_tiles``
    is accepted and ignored: the kernel walks live tiles itself, so one
    program serves every span and the engine passes none (the benchmark
    harness's naming test does, PERF.md §7)."""
    if prev_nxt is not None:
        tokens = jnp.where(feed_host, tokens, prev_nxt[:tokens.shape[0]])
    positions = (lengths - 1)[:, None]
    logits, cache, counts = _apply(model, variables, tokens[:, None],
                                   positions=positions, cache=cache,
                                   cache_index=lengths - 1, slot_mask=active,
                                   attention_backend=attention_backend,
                                   paged_tile=paged_tile)
    key, sub = jax.random.split(key)
    nxt = sample_logits(logits[:, 0], sub, temperature, top_k, top_p)
    if counts is not None:
        nxt = jnp.concatenate([nxt, counts.astype(nxt.dtype)])
    return cache, nxt, key


@functools.partial(jax.jit, static_argnames=(
    "model", "attention_backend", "paged_num_tiles", "paged_tile"),
    donate_argnums=(2,))
def _verify_step_jit(model: LlamaModel, variables: Any, cache: Any,
                     tokens: jnp.ndarray, lengths: jnp.ndarray,
                     active: jnp.ndarray,
                     attention_backend: str = "dense",
                     paged_num_tiles: Optional[int] = None,
                     paged_tile: Optional[int] = None):
    """One speculative VERIFY step: feed every slot its pending token
    plus its drafted span (``tokens`` is ``(n_slots, S)`` — column 0
    the pending token, columns 1..S-1 the draft, pad beyond) at
    positions ``lengths-1 .. lengths-1+S-1``, and return the model's
    greedy continuation at EVERY position (``(n_slots, S)`` int32).

    The host accepts the longest prefix where draft == greedy and
    commits ``accepted + 1`` tokens — one compiled program per S
    bucket, costing one model forward however many tokens it commits.
    Writes ride the same slot_mask-gated batched scatter as the plain
    step; a REJECTED draft position's K/V lands beyond the committed
    length, where the junk-write invariant already holds (overwritten
    before it is ever attendable).  Greedy only: acceptance compares
    argmax, which is exactly the temperature-0 sampling rule.
    ``paged_num_tiles``: accepted and ignored, as in
    :func:`_decode_step_jit`.  A model with expert layers appends the rows
    whose first entries are its counts (:func:`_apply`; they count the
    drafted tokens too, rejected or not: each was routed)."""
    positions = (lengths - 1)[:, None] + jnp.arange(tokens.shape[1])[None, :]
    logits, cache, counts = _apply(model, variables, tokens,
                                   positions=positions, cache=cache,
                                   cache_index=lengths - 1, slot_mask=active,
                                   attention_backend=attention_backend,
                                   paged_tile=paged_tile)
    g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if counts is not None:
        rows = -(-counts.shape[0] // g.shape[1])
        g = jnp.concatenate([g, jnp.pad(
            counts, (0, rows * g.shape[1] - counts.shape[0])).reshape(
                rows, g.shape[1])])
    return cache, g


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_prefix_jit(cache: Any, src: jnp.ndarray, dst: jnp.ndarray,
                     length: Any):
    """Copy K/V positions ``[0, length)`` of slot ``src`` into slot
    ``dst`` (the longest-common-prefix reuse transfer).  ``length`` is a
    count of positions, or (an engine whose kinds keep packed rows or a
    ring: :meth:`SlotEngine._copy_length`) a tree like ``cache`` of each
    entry's count of ROWS: a packed entry's flat rows, a ring whole."""
    def cp(c, n):
        row = lax.dynamic_slice_in_dim(c, src, 1, axis=0)
        old = lax.dynamic_slice_in_dim(c, dst, 1, axis=0)
        m = (jnp.arange(c.shape[1]) < n)[None, :, None, None] \
            if c.ndim == 4 else (jnp.arange(c.shape[1]) < n)[None, :, None]
        return lax.dynamic_update_slice_in_dim(
            c, jnp.where(m, row, old), dst, axis=0)
    if isinstance(length, list):
        return jax.tree.map(cp, cache, length)
    return jax.tree.map(lambda c: cp(c, length), cache)


@functools.partial(jax.jit, donate_argnums=(0,))
def _restore_span_jit(cache: Any, rows: Any, slot: jnp.ndarray):
    """Write a host-restored K/V span (``rows`` — per-layer ``k``/``v``
    of shape ``(bucket, kv_heads, d_head)``, padded to a prefill
    bucket) into positions ``[0, bucket)`` of row ``slot``.  No mask:
    the pad rows land at positions the junk-write invariant already
    covers (>= the restored ``kv_len``, overwritten by the tail prefill
    or never attendable)."""
    def wr(c, r):
        return lax.dynamic_update_slice(c, r[None], (slot, 0, 0, 0))
    return jax.tree.map(wr, cache, rows)


#: from a bucket of this many rows on, a prefill pass of every served
#: configuration is bound by its products (some 4x the v5e's ridge of about
#: 240 rows a bfloat16 weight), so a padded row costs what a real one does
_MID_BUCKET_FROM = 1024


def prefill_buckets(max_len: int, floor: int) -> Tuple[int, ...]:
    """The prefill bucket lattice of an engine: ``floor`` doubled while it
    is under ``max_len``, then ``max_len``, so the prefill compiles
    O(log max_len) programs however ragged the traffic.  Where the last
    doubling ``p`` is ``_MID_BUCKET_FROM`` or more and ``3p/2`` is under
    ``max_len``, one bucket of ``3p/2`` goes between them: the top octave
    is where padding costs the most rows, and each bucket is one more
    program to warm."""
    buckets = []
    b = max(1, int(floor))
    while b < max_len:
        buckets.append(b)
        b *= 2
    if buckets and buckets[-1] >= _MID_BUCKET_FROM \
            and 3 * buckets[-1] // 2 < max_len:
        buckets.append(3 * buckets[-1] // 2)
    buckets.append(max_len)
    return tuple(buckets)


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n — the ONE round-up behind the verify
    S bucket and the VMEM gate's widest-span pricing (they must agree,
    or the gate admits geometries the verify launch exceeds)."""
    p = 1
    while p < n:
        p *= 2
    return p


def _decode_program_key(backend: str) -> str:
    """Stable label for the compiled decode-step program — THE naming
    contract between the step dispatch below and the warmup lattice
    (:mod:`~synapseml_tpu.models.llm.warmup` imports these, so the
    lattice can never warm under one name what serving runs under
    another)."""
    return f"decode_{backend}"


def _verify_program_key(backend: str, s: int) -> str:
    """Stable label for the compiled verify program of span bucket S."""
    return f"verify_{backend}_s{s}"


def _prefill_program_key(pb: int) -> str:
    """Stable label for one compiled prefill-bucket program."""
    return f"prefill_b{pb}"


def _restore_program_key(pb: int) -> str:
    """Stable label for one compiled host-restore program (one per
    prefill bucket — the restored span pads to the same grid)."""
    return f"restore_b{pb}"


@dataclasses.dataclass
class AdmitResult:
    """What :meth:`SlotEngine.admit` hands back: the slot, the FIRST
    generated token (prefill produces it immediately — this is the
    time-to-first-token moment), whether the sequence already finished
    (eos on token one / budget of one), how many prompt tokens were
    served from a reused prefix, and the prefill's last-token logits
    (f32 host copy — the prefix-reuse exactness surface).  ``bucket``
    (the padded prefill bucket) and ``reason`` (the finish verdict,
    when ``finished``) feed the request-scoped trace the serving loop
    keeps per request; ``path`` says where the prompt's K/V came from
    (``cold``: all prefilled, ``reuse``: a device-resident prefix,
    ``restore``: the host arena, ``cold_recurrent``: a prefix was there to
    reuse and was prefilled again, because the model's recurrent state
    after it was not, ``cold_ring``: a prefix was there and was prefilled
    again, because the source slot's ring no longer held the window's rows
    before the prefix's end)."""
    slot: int
    token: int
    finished: bool
    reused_tokens: int
    logits: np.ndarray
    bucket: int = 0
    reason: Optional[str] = None
    path: str = "cold"


@dataclasses.dataclass
class StepEvent:
    """One slot's outcome of a decode step."""
    slot: int
    token: int
    finished: bool
    reason: Optional[str] = None      # "eos" | "length" when finished


@dataclasses.dataclass
class _Flight:
    """A one-token step the device has and the host has not read."""
    nxt: Any                  # (n_slots,) int32, on the device
    slots: np.ndarray         # the slots it advances
    epoch: np.ndarray         # their admission epochs at dispatch
    lengths: np.ndarray       # the lengths it was fed (other slots: 1)
    program: str


@dataclasses.dataclass(frozen=True)
class _KindCache:
    """What the cache entries of ONE attention layer kind are: how many
    layers, their window, the rows a slot keeps (a ring's, on one), the
    heads and widths of a row, how the entry packs them, and the paged
    kernel's geometry over it (None: the dense backend)."""
    kind: str
    layers: int
    window: Optional[int]
    rows: int
    ring: bool
    kv_heads: int
    d_key: int
    d_value: int
    packed: bool
    pack: int
    #: heads and lanes the cache really holds a position (its padding
    #: included), for the kernel's byte ledger
    row_heads: int
    geo: Optional[PagedGeometry] = None
    #: latent rows (``model.LatentAttention``): one head, ``d_key`` the
    #: latent's width, no separate value (the row's first lanes)
    latent: bool = False

    @property
    def geometry_args(self) -> Dict[str, Any]:
        """:func:`paged_geometry`'s keywords for this kind."""
        kw: Dict[str, Any] = {}
        if self.latent:
            kw.update(latent=True)
        if self.packed:
            kw.update(d_value=self.d_value, pack=self.pack)
        if self.ring:
            kw.update(most=RING_BLOCK)
        return kw

    def row_bytes(self, itemsize: int) -> int:
        """K and V bytes of one position, unpadded: what attention needs."""
        return self.kv_heads * (self.d_key + self.d_value) * itemsize

    def held_row_bytes(self, itemsize: int) -> int:
        """K and V bytes the cache holds a position a layer (a row's padding
        heads, and a latent row's padding lanes, included)."""
        if self.latent:
            return -(-self.d_key // 128) * 128 * itemsize
        return self.row_heads * (self.d_key + self.d_value) * itemsize


class SlotEngine:
    """Continuous-batching decode engine over a slotted KV cache.

    Single-threaded by contract: one serving loop owns the engine and
    interleaves :meth:`admit` / :meth:`step` freely — a sequence
    admitted mid-flight decodes next to longer-running neighbors in
    the same jitted step.  Greedy output is token-exact
    with the dense-cache ``generate`` path.  The public state
    (``active``, ``lengths``, ``ctx``, ``kv_len``) is what the returned
    events have made true; a step already handed to the device is not
    in it, and a sequence admitted while one is in flight joins the
    step after it.
    """

    def __init__(self, model: LlamaModel, variables: Any,
                 n_slots: int = 16, max_len: Optional[int] = None, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, eos_id: Optional[int] = None,
                 pad_id: int = 0, min_prefix: int = 8,
                 min_bucket: Optional[int] = None, seed: int = 0,
                 name: str = "llm",
                 attention_backend: str = "auto",
                 spec_draft_len: int = 0, spec_ngram: int = 3,
                 spec_adapt: bool = True, trace_sink=None,
                 warmup: str = "off", kv_arena=None):
        self.model = model
        self.variables = variables
        self.cfg = model.cfg
        self.n_slots = int(n_slots)
        self.max_len = int(max_len or self.cfg.max_len)
        # decode-attention backend: 'auto' resolves to the Pallas paged
        # kernel on TPU when the geometry fits VMEM, dense otherwise;
        # 'paged'/'interpret' fail fast when they cannot run (the
        # resolve_collective_config validation idiom)
        # the widest verify step a spec-enabled engine can launch (the
        # pow2 S bucket over pending + longest draft) — the VMEM gate
        # must price ITS q/scratch working set, not the S=1 step's
        spec_span = _next_pow2(1 + max(0, int(spec_draft_len)))
        #: one record an attention layer kind: its cache entry and geometry
        self._kinds = self._describe_kinds()
        backends = {resolve_attention_backend(
            attention_backend, max_len=kc.rows,
            num_heads=self.cfg.num_heads, num_kv_heads=kc.kv_heads,
            d_head=kc.d_key, dtype=self.cfg.dtype,
            max_query_span=spec_span, **kc.geometry_args)
            for kc in self._kinds} or {resolve_attention_backend(
                attention_backend, max_len=self.max_len,
                num_heads=self.cfg.num_heads,
                num_kv_heads=self.cfg.num_kv_heads, d_head=self.cfg.d_head,
                dtype=self.cfg.dtype, max_query_span=spec_span)}
        # 'auto' is paged where every kind has a geometry
        self.attention_backend = backends.pop() if len(backends) == 1 \
            else "dense"
        #: layers whose state cannot be sliced by token position (module
        #: docstring, "Two kinds of state")
        self.recurrent = self.cfg.num_recurrent_layers > 0
        #: window layers on a ring: rows by position exist for the last
        #: ``ring_rows`` positions only (module docstring, "On a ring")
        self.ring = any(kc.ring for kc in self._kinds)
        #: every attention entry is ``(slots, max_len, heads, d_head)`` rows
        #: by position, which the host arena and a prefill worker slice
        self.kv_by_position = not self.recurrent and not any(
            kc.ring or kc.packed or kc.latent for kc in self._kinds)
        #: the model has expert layers: its programs return three counts
        #: with their tokens (:func:`_apply`)
        self.experts = self.cfg.has_experts
        if self.recurrent:
            if spec_draft_len:
                raise ValueError(
                    "spec_draft_len > 0 with linear-attention layers: a "
                    "verify step writes its whole drafted span and rolls "
                    "the rejected part back by position; a recurrent state "
                    "has taken those tokens for good and keeps no snapshot "
                    "to return to")
            if kv_arena is not None:
                raise ValueError(
                    "kv_arena with linear-attention layers: the host arena "
                    "spills and restores K/V rows by token position; a "
                    "recurrent state can be snapshotted at a position, not "
                    "sliced, and the engine builds no snapshots")
            resolve_recurrent_backend(
                self.attention_backend, self.cfg.linear_num_heads,
                self.cfg.linear_key_head_dim, self.cfg.linear_value_head_dim)
        if self.ring and spec_draft_len:
            raise ValueError(
                "spec_draft_len > 0 with window layers on a ring: a verify "
                "step writes its whole drafted span, and on a ring the "
                "rejected part's rows have overwritten the rows a window "
                "behind them; the ring is sized for one written row a step")
        if kv_arena is not None and not self.kv_by_position:
            raise ValueError(
                "kv_arena over a cache that is not rows by position (window "
                "layers on a ring, kinds that keep packed rows, or latent "
                "rows): the host arena spills and restores a slot's K/V rows "
                "[0, span), which a ring no longer holds and a packed or "
                "latent entry lays out otherwise")
        if self.attention_backend != "dense":
            self._kinds = [dataclasses.replace(kc, geo=paged_geometry(
                kc.rows, self.cfg.num_heads, kc.kv_heads, kc.d_key,
                self.cfg.dtype, max_query_span=spec_span,
                **kc.geometry_args)) for kc in self._kinds]
        #: the first kind's geometry (every kind's where the model has one
        #: kind of cache entry): what the tuning table is consulted for
        self._paged_geo = self._kinds[0].geo if self._kinds else None
        # tuned K/V tile: the ``paged_attn_tile`` tuning-table winner
        # for THIS cache geometry, admitted only through the same
        # divisibility/VMEM gate the ladder uses — no table (or a tile
        # the gate rejects) keeps the default geometry, so dispatch is
        # program-key-identical to a table-less process.  A model whose
        # kinds differ in geometry keeps each kind's default
        if self._paged_geo is not None and self._one_geometry:
            self._paged_geo = self._consult_paged_tile(
                spec_span, self._paged_geo)
            self._kinds = [dataclasses.replace(kc, geo=self._paged_geo)
                           for kc in self._kinds]
        #: the resolved K/V tile as the programs' static ``paged_tile``: one
        #: number where every kind's geometry is one, else ``((kind, tile),
        #: ...)``; None on the dense backend
        self._paged_tile: Any = None if self._paged_geo is None \
            else self._paged_geo.tile if self._one_geometry \
            else tuple((kc.kind, kc.geo.tile) for kc in self._kinds)
        #: optional request-trace hook ``sink(slot, event, **attrs)`` —
        #: the serving loop installs one mapping slots to trace ids.  The
        #: engine reports a slot's TRANSITIONS through it and nothing a
        #: token: ``decode`` at the first step that gives the slot's
        #: occupant a token (``tokens``: how many), and ``retired`` when
        #: the occupant leaves the slot, with its totals (:meth:`_retire`).
        #: None costs one attribute check a step.
        self.trace_sink = trace_sink
        #: key of the program that ran the step last returned (the
        #: ``engine.step`` span's ``program``)
        self.last_program: Optional[str] = None
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.min_prefix = max(1, int(min_prefix))
        self.name = name
        # speculative decoding: n-gram self-drafts verified in a
        # multi-token step (spec_draft_len == 0: every step is the plain
        # one-token step)
        self.spec_draft_len = max(0, int(spec_draft_len))
        self.spec_adapt = bool(spec_adapt)
        if self.spec_draft_len and self.temperature > 0:
            raise ValueError(
                "spec_draft_len > 0 requires greedy decoding "
                "(temperature <= 0): speculative verification accepts a "
                "draft token only when it equals the model's argmax, "
                "which is only the sampling rule at temperature 0")
        self._drafter = (NgramDrafter(int(n_slots), ngram=int(spec_ngram))
                         if self.spec_draft_len else None)
        self._key = jax.random.PRNGKey(seed)
        self.cache = init_cache(self.cfg, self.n_slots, self.max_len)
        # prompt-length buckets (:func:`prefill_buckets`).  The grid
        # floor defaults to 8; an explicit min_bucket wins outright, and
        # the None sentinel consults the ``llm_bucket_grid`` tuning
        # table (absent/mismatched table → 8)
        if min_bucket is None:
            min_bucket = self._consult_min_bucket()
        self._buckets = prefill_buckets(self.max_len, min_bucket)
        # host-side slot state (one serving loop owns these, no locks)
        n = self.n_slots
        self.ctx = np.zeros((n, self.max_len), np.int32)   # incl. pending tok
        self.lengths = np.zeros(n, np.int64)               # tokens in ctx
        self.active = np.zeros(n, bool)
        self.kv_len = np.zeros(n, np.int64)                # valid K/V rows
        self._retired_at = np.full(n, -np.inf)             # reclaim recency
        self._max_new = np.zeros(n, np.int64)
        self._generated = np.zeros(n, np.int64)
        #: a slot's occupant since its admission or resume: steps that gave
        #: it a token, the verify steps among them, positions drafted for
        #: it and accepted (``trace_sink``'s ``retired`` hands them over)
        self._slot_totals = np.zeros((n, 4), np.int64)
        #: seconds the host has spent in a step's three parts since the
        #: engine was built, kept beside the ``engine.step.*`` spans and
        #: always (four clock reads a step): ``prepare`` is host arrays,
        #: uploads and the dispatch's enqueue, ``wait`` the blocking read
        #: of a step's tokens (the host's slack under the device's step
        #: where steps overlap), ``commit`` the slot loop and retirement
        self.phase_seconds = {"prepare": 0.0, "wait": 0.0, "commit": 0.0}
        #: the step dispatched and not yet read, and what tells its
        #: output apart from a slot's later occupant: a count of the
        #: slot's admissions and resumes
        self._flight: Optional[_Flight] = None
        self._epoch = np.zeros(n, np.int64)
        # ``prev_nxt`` of a first step (an expert model's carries its counts)
        self._no_prev = jnp.zeros(
            n + (len(EXPERT_COUNTS) if self.experts else 0), jnp.int32)
        # radix prefix indices over slot contexts, ONE PER TENANT:
        # longest_prefix is exact by construction (tokens, not hashes),
        # so reuse finds the TRUE longest match with no candidate probe
        # and no first-min_prefix-tokens blind spot — and a lookup can
        # only ever match a slot the SAME tenant filled, so identical
        # prompts from two tenants never share device K/V
        self._radices: Dict[str, RadixPrefixIndex] = {}
        #: per-slot owning tenant (admission sets it; sticky through
        #: retirement so the retired prefix stays in its owner's index)
        self._slot_tenant: List[str] = ["default"] * n
        #: slot -> tenant whose radix currently indexes the slot
        self._slot_radix: Dict[int, str] = {}
        #: optional :class:`~synapseml_tpu.models.llm.kvtier
        #: .HostKVArena` — when attached, ``_retire`` spills the slot's
        #: live K/V span to host RAM and ``admit`` restores warm
        #: conversations from it instead of recomputing prefill
        #: (token-exact; every degraded path cold-prefills)
        self.kv_arena = kv_arena
        self._mkv = kvtier_metrics()
        # per-slot draft-length adaptation (AIMD over a rolling
        # acceptance EWMA): caps start at a cheap 2-token probe, DOUBLE
        # on a fully-accepted draft, HALVE when under half the draft
        # survives, and collapse to a 1-token probe on persistent
        # badness (EWMA < 0.2) — so predictable text climbs to the
        # full cap in ~log2(spec_draft_len) steps while mediocre text
        # keeps its drafts short (expected acceptance of a k-token
        # draft falls with k when the per-token match probability is
        # middling, so short drafts are what keep acceptance — and the
        # verify width's cost — honest)
        self._spec_k0 = min(2, self.spec_draft_len) if self.spec_draft_len \
            else 0
        self._spec_k = np.full(n, self._spec_k0, np.int64)
        self._spec_ewma = np.ones(n)
        reg = get_registry()
        self._m_admit = reg.counter(
            "llm_admissions_total", "sequences admitted into a slot",
            ("engine", "tenant"))
        self._m_evict = reg.counter(
            "llm_evictions_total", "sequences retired from a slot",
            ("engine", "reason", "tenant"))
        self._m_reuse = reg.counter(
            "llm_prefix_reuse_total", "admissions served a reused prefix",
            ("engine",))
        self._m_reuse_tok = reg.counter(
            "llm_prefix_tokens_reused_total",
            "prompt tokens copied from a cached prefix instead of "
            "prefilled", ("engine",))
        self._m_reuse_skipped = reg.counter(
            "llm_prefix_reuse_skipped_total",
            "admissions and resumes that found a reusable prefix and "
            "prefilled it anyway (reason recurrent_state: the model keeps "
            "a state that cannot be sliced by token position; "
            "ring_overwritten: the source slot's ring no longer holds the "
            "window's rows before the prefix's end)",
            ("engine", "reason"))
        self._m_occ = reg.gauge(
            "llm_slot_occupancy", "active slots / total slots", ("engine",))
        #: bytes of recurrent state and convolution window one slot holds
        #: over all linear-attention layers (0 for a model without them)
        #: (the state by value heads, the window's q and k by key heads)
        self.slot_state_bytes = self.cfg.num_recurrent_layers * \
            slot_state_bytes(
                self.cfg.linear_num_heads, self.cfg.linear_key_head_dim,
                self.cfg.linear_value_head_dim,
                self.cfg.linear_conv_kernel_dim - 1,
                linear_conv_channels(self.cfg),
                np.dtype(self.cfg.dtype).itemsize)
        reg.gauge(
            "llm_recurrent_state_bytes",
            "device bytes of recurrent state and convolution windows the "
            "engine holds beside its K/V cache (every slot, every "
            "linear-attention layer)", ("engine",)
        ).set(self.n_slots * self.slot_state_bytes, engine=name)
        itemsize = np.dtype(self.cfg.dtype).itemsize
        reserved = reg.gauge(
            "llm_kv_cache_bytes_reserved",
            "device bytes of K/V rows the engine's cache holds for one "
            "attention layer kind (every slot, every layer of the kind; a "
            "window layer on a ring holds its ring's rows)",
            ("engine", "kind"))
        self._m_kv_in_use = reg.gauge(
            "llm_kv_cache_bytes_in_use",
            "bytes of those rows that hold a live key or value of a slot in "
            "use at the last step (a ring's rows fill up to the ring)",
            ("engine", "kind"))
        for kc in self._kinds:
            reserved.set(kc.layers * self.n_slots * kc.rows
                         * kc.held_row_bytes(itemsize),
                         engine=name, kind=kc.kind)
        self._m_prefill_attn = reg.counter(
            "llm_prefill_attention_total",
            "prefill passes by the path their attention took (tiled: the "
            "Pallas kernel on every attention layer kind; dense: the plain "
            "scores on every kind; mixed: the kernel on the kinds whose "
            "shape has a tile)", ("engine", "path"))
        self._m_prefill_rows = reg.counter(
            "llm_prefill_rows_total",
            "rows prefill passes computed, by whether they held a prompt "
            "token (real) or the bucket's padding past it (padding)",
            ("engine", "rows"))
        #: (bucket, from position 0) -> what a prefill pass's attention runs
        #: as (:meth:`_prefill_plan`)
        self._prefill_plans: Dict[Tuple[int, bool], Tuple[str, Tuple]] = {}
        self._m_latent_prefill = reg.counter(
            "llm_latent_prefill_total",
            "prefill passes of a model with latent attention layers by the "
            "form their attention took: cold (from position 0, the pass's "
            "own rows expanded), expanded (a tail after a cached prefix, "
            "every row of the slot expanded to all heads), absorbed (a tail "
            "after a cached prefix over the latent rows themselves)",
            ("engine", "form"))
        #: the last prefill pass (bucket, start, real tokens), for its span
        self._last_prefill: Tuple[int, int, int] = (0, 0, 0)
        self._m_expert_pairs = reg.counter(
            "llm_expert_pairs_total",
            "(token, expert) pairs whose expert this program holds, computed "
            "in decode steps and prefills (summed over layers)", ("engine",))
        self._m_experts_touched = reg.counter(
            "llm_experts_touched_total",
            "held experts with at least one pair, summed over layers and "
            "over decode steps and prefills: the expert weights read",
            ("engine",))
        self._m_expert_rows = reg.counter(
            "llm_expert_rows_total",
            "rows of the tiles the grouped expert product computed, summed "
            "over layers and over decode steps and prefills: rows=pairs "
            "held a (token, expert) pair, rows=padding filled a tile past "
            "its expert's pairs", ("engine", "rows"))
        if self.experts:
            cfg = self.cfg
            reg.gauge(
                "llm_expert_weight_bytes_held",
                "device bytes of the routed experts this program holds "
                "(every expert layer)", ("engine",)
            ).set(cfg.num_expert_layers * cfg.experts_held_count * 3
                  * cfg.d_model
                  * (cfg.expert_d_ff or cfg.d_ff)
                  * np.dtype(cfg.dtype).itemsize, engine=name)
        #: the last read program's expert counts, for its span
        self._step_experts: Dict[str, int] = {}
        self._m_overlap = reg.counter(
            "llm_steps_overlapped_total",
            "decode steps dispatched before the previous step's tokens "
            "were read (the device ran them under the host's work)",
            ("engine",))
        self._m_decode_bytes = reg.gauge(
            "llm_decode_bytes_per_token",
            "decode-attention K/V bytes read per generated token this "
            "step (exact DMA ledger for the paged kernel; the full-"
            "capacity read model for dense)", ("engine", "backend"))
        self._m_spec_span = reg.histogram(
            "llm_spec_accepted_span_size",
            "tokens committed per slot per speculative verify step "
            "(accepted draft prefix + the bonus token)", ("engine",),
            buckets=(1, 2, 3, 4, 5, 6, 8, 12, 16))
        self._m_spec_hit = reg.counter(
            "llm_spec_draft_hit_total",
            "slot-steps where the n-gram drafter proposed a span",
            ("engine",))
        self._m_spec_miss = reg.counter(
            "llm_spec_draft_miss_total",
            "slot-steps where the n-gram drafter had no match (the slot "
            "rode the plain one-token step)", ("engine",))
        self.admissions = 0
        self.evictions = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        self.prefix_reuse_skipped = 0
        self.tokens_generated = 0
        # the compile plane: 'sync' blocks construction until the full
        # program lattice — every prefill bucket, the decode step, every
        # verify width, and the prefix copy — is AOT-compiled;
        # 'background' warms on a daemon thread (serve readiness through
        # compile_plane.is_warm / the LLMServer /readyz gate); 'off'
        # compiles each program at its first call.  Programs are warmed
        # through the REAL
        # jitted entry points against scratch state, so the first
        # serving hit is a dispatch-cache hit, not a compile.
        if warmup in (None, False):
            warmup = "off"
        elif warmup is True:
            warmup = "sync"
        if warmup not in ("off", "sync", "background"):
            raise ValueError(
                f"warmup={warmup!r}: must be 'off', 'sync', or "
                "'background'")
        self.compile_plane = None
        if warmup != "off":
            from .warmup import CompilePlane
            self.compile_plane = CompilePlane(self, name=name)
            self.compile_plane.start(background=(warmup == "background"))
        #: cumulative decode-attention K/V bytes (the ledger feeding the
        #: ``llm_decode_bytes_per_token`` gauge)
        self.decode_attn_bytes = 0
        #: the last accounted step's tile counts (paged backends), for
        #: the ``engine.step`` span
        self._step_tiles: Dict[str, int] = {}
        #: step accounting: steps_run counts EVERY engine step (plain
        #: or verify), spec_* only drafted work
        #: (``tokens_per_step_estimate`` reads them); steps_overlapped
        #: those of them dispatched a step ahead
        self.steps_run = 0
        self.steps_overlapped = 0
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_draft_hits = 0
        self.spec_draft_misses = 0
        self._tps_ewma: Optional[float] = None

    # -- tuning-table consults ---------------------------------------------
    def _consult_paged_tile(self, spec_span: int, default_geo):
        """``paged_attn_tile`` winner for this cache geometry → the
        tuned :class:`PagedGeometry`, or the default when the table is
        absent/mismatched/stale or the winner fails the VMEM gate."""
        from .pallas_attn import paged_geometry_key
        from ...telemetry.tunetable import get_tuneplane
        kc = self._kinds[0]

        def geometry(tile):
            return paged_geometry(
                kc.rows, self.cfg.num_heads, kc.kv_heads, kc.d_key,
                self.cfg.dtype, max_query_span=spec_span, tile=tile,
                **kc.geometry_args)

        def _gate(winner):
            t = winner.get("tile")
            return (isinstance(t, int) and not isinstance(t, bool)
                    and geometry(t) is not None)

        winner = get_tuneplane().consult(
            "SlotEngine", "paged_attn_tile",
            paged_geometry_key(kc.rows, kc.kv_heads, kc.d_key,
                               self.cfg.dtype, spec_span),
            validate=_gate)
        if winner is None:
            return default_geo
        return geometry(int(winner["tile"]))

    def _consult_min_bucket(self) -> int:
        """``llm_bucket_grid`` winner for this ``max_len`` → the tuned
        bucket-grid floor, or the default 8."""
        from ...telemetry.tunetable import geometry_key, get_tuneplane
        winner = get_tuneplane().consult(
            "SlotEngine", "llm_bucket_grid",
            geometry_key(max_len=self.max_len),
            validate=lambda w: (
                isinstance(w.get("min_bucket"), int)
                and not isinstance(w["min_bucket"], bool)
                and 1 <= w["min_bucket"] <= self.max_len
                and (w["min_bucket"] & (w["min_bucket"] - 1)) == 0))
        return int(winner["min_bucket"]) if winner is not None else 8

    # -- the cache by layer kind ---------------------------------------------
    def _describe_kinds(self) -> List[_KindCache]:
        """One :class:`_KindCache` an attention layer kind the model has, in
        layer order (none for a model of linear-attention layers alone)."""
        cfg, out = self.cfg, []
        for kind in cfg.attention_layer_kinds:
            if kind == "latent_attention":
                width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
                out.append(_KindCache(
                    kind=kind, layers=cfg.layer_kinds.count(kind), window=None,
                    rows=self.max_len, ring=False, kv_heads=1, d_key=width,
                    d_value=0, packed=False, pack=1, row_heads=1,
                    latent=True))
                continue
            a = cfg.attention(kind)
            rows = MIXERS[kind].cache_rows(cfg, self.max_len)
            window = cfg.sliding_window if kind == "sliding_attention" \
                else None
            packed = cfg.packed(kind)
            out.append(_KindCache(
                kind=kind, layers=cfg.layer_kinds.count(kind), window=window,
                rows=rows, ring=window is not None and rows < self.max_len,
                kv_heads=a.num_kv_heads, d_key=a.head_dim,
                d_value=a.v_head_dim, packed=packed,
                pack=kv_pack(a.head_dim, a.num_kv_heads) if packed else 1,
                row_heads=a.num_kv_heads if packed else cfg.kv_cache_heads))
        return out

    @property
    def _one_geometry(self) -> bool:
        """Every kind's cache entry has the same rows, heads and widths (a
        model of one kind, or of kinds that differ in their masks alone)."""
        return len({(kc.rows, kc.kv_heads, kc.d_key, kc.d_value, kc.pack,
                     kc.ring) for kc in self._kinds}) <= 1

    def _copy_length(self, positions: int) -> Any:
        """``_copy_prefix_jit``'s ``length`` for a prefix of ``positions``
        tokens: the count itself where every entry is rows by position, else
        each entry's count of rows (a packed entry's flat rows; a ring
        whole: it is copied as it stands; a latent entry's rows by
        position)."""
        if self.kv_by_position or self.recurrent:
            return positions
        by_kind = {kc.kind: kc for kc in self._kinds}
        out = []
        for kind in self.cfg.layer_kinds:
            kc = by_kind[kind]
            per = (kc.kv_heads // kc.pack) if kc.packed else 1
            n = kc.rows * per if kc.ring else positions * per
            out.append({"latent": n} if kc.latent else {"k": n, "v": n})
        return out

    # -- capacity ----------------------------------------------------------
    @property
    def active_count(self) -> int:
        return int(self.active.sum())

    @property
    def free_slot_count(self) -> int:
        return self.n_slots - self.active_count

    # -- compile plane -----------------------------------------------------
    def _program_region(self, key: str):
        """Wrap one jitted serving call: attributes any compile inside
        it to ``key`` and counts in-loop compiles as stalls.  A plane-
        less engine pays nothing (nullcontext)."""
        plane = self.compile_plane
        return (contextlib.nullcontext() if plane is None
                else plane.step_region(key))

    def admission_ready(self, prompt_len: int) -> bool:
        """Would admitting a ``prompt_len``-token prompt stall on an
        XLA compile?  Always True without a compile plane (lazy
        compiles are the pre-plane contract) and once the plane is
        warm; during a background warmup, True only when the prompt's
        prefill bucket and the decode/copy/verify base programs are
        compiled (a cold bucket is bumped to the front of the
        remaining lattice).  The serving loop holds not-ready requests
        in queue
        — exempt from SLO shedding — instead of admitting them into a
        compile stall."""
        plane = self.compile_plane
        return plane is None or plane.admission_ready(prompt_len)

    def min_remaining_tokens(self) -> Optional[int]:
        """Smallest remaining token budget across active slots — the
        soonest a slot can free up (the SLO-projection numerator).  None
        when no slot is active."""
        if not self.active.any():
            return None
        rem = (self._max_new - self._generated)[self.active]
        return int(rem.min())

    def tokens_per_step_estimate(self) -> float:
        """Committed tokens per engine step, EWMA over recent steps —
        >= 1.0 always (a plain step commits one token per active slot).
        The serving loop divides its remaining-token floor by this so
        SLO projections track SPEC throughput (remaining-tokens /
        accepted-tokens-per-step) instead of assuming one token per
        step."""
        return max(1.0, self._tps_ewma or 1.0)

    @property
    def spec_acceptance_rate(self) -> float:
        """Accepted / drafted tokens, cumulative — only REAL drafts
        count (a drafter miss costs no verify positions and dilutes
        nothing)."""
        return self.spec_accepted / max(1, self.spec_drafted)

    # -- prefix reuse ------------------------------------------------------
    def _radix_for(self, tenant: str) -> RadixPrefixIndex:
        idx = self._radices.get(tenant)
        if idx is None:
            idx = self._radices[tenant] = RadixPrefixIndex()
        return idx

    def _register_prefix(self, slot: int, ids: np.ndarray) -> None:
        tenant = self._slot_tenant[slot]
        prev = self._slot_radix.get(slot)
        if prev is not None and prev != tenant:
            # the slot changed hands: its old owner's index must not
            # keep pointing at K/V the new owner is about to overwrite
            idx = self._radices.get(prev)
            if idx is not None:
                idx.remove(slot)
            del self._slot_radix[slot]
        if len(ids) < self.min_prefix:
            idx = self._radices.get(tenant)
            if idx is not None:
                idx.remove(slot)
            self._slot_radix.pop(slot, None)
        else:
            self._radix_for(tenant).insert(ids, slot)
            self._slot_radix[slot] = tenant

    def _unregister_prefix(self, slot: int) -> None:
        prev = self._slot_radix.pop(slot, None)
        if prev is not None:
            idx = self._radices.get(prev)
            if idx is not None:
                idx.remove(slot)

    def _clamp_reuse(self, lcp: int, total: int) -> int:
        """Shrink a reuse length until the remaining tail's PADDED
        prefill bucket fits inside ``max_len`` — without the clamp a
        long reuse pushes ``start + bucket`` past the cache end and
        ``dynamic_update_slice`` silently CLAMPS the write start,
        corrupting the reused prefix K/V.  ``lcp == total`` (a full
        restore, no tail to prefill) passes through untouched."""
        if lcp >= total:
            return min(lcp, total)
        while lcp >= self.min_prefix \
                and lcp + self._bucket(total - lcp) > self.max_len:
            # terminates — lcp strictly decreases (the violated bound
            # implies lcp > max_len - bucket)
            lcp = self.max_len - self._bucket(total - lcp)
        return max(0, lcp)

    def _best_prefix(self, prompt: np.ndarray,
                     dst: int) -> Tuple[Optional[int], int]:
        """Longest common prefix between ``prompt`` and any indexed
        slot's context — one radix walk, exact by construction (the
        trie compares tokens, so no collision can smuggle wrong K/V
        and no hash window hides a longer match).  Reuse is capped at
        ``len(prompt) - 1``: the prefill must always run at least one
        token to produce next-token logits.

        ``dst`` itself is a valid source — the multi-turn sweet spot
        where the reclaimed slot already holds the conversation's
        earlier turns: the K/V is already in place, so the admit skips
        the copy and just prefills the tail (``dst`` wins ties for
        that reason).  The returned lcp is additionally bucket-clamped
        (:meth:`_clamp_reuse`).  The walk is scoped to the admitting
        slot's TENANT index — another tenant's identical tokens are
        never a reuse source."""
        radix = self._radices.get(self._slot_tenant[dst])
        if radix is None:
            return None, 0
        src, lcp = radix.longest_prefix(prompt, prefer=dst)
        if src is None:
            return None, 0
        lcp = int(min(lcp, self.kv_len[src], len(prompt) - 1))
        lcp = self._clamp_reuse(lcp, len(prompt))
        if lcp < self.min_prefix:
            return None, 0
        return src, lcp

    def _reuse_refused(self, src: int, lcp: int) -> Optional[str]:
        """Why ``lcp`` tokens of slot ``src`` cannot be reused, or None:
        ``recurrent_state`` (its K/V rows could be copied, the recurrent
        state after its last token was never kept) or ``ring_overwritten``
        (a window layer's ring holds the last ``rows`` positions the slot
        wrote: the tail's first query needs the ``sliding_window`` rows
        before ``lcp``, and the source has since written more than the
        ring's spare rows past it; two of those are kept for the steps that
        may be in flight over an active source)."""
        if self.recurrent:
            return "recurrent_state"
        for kc in self._kinds:
            if kc.ring and int(self.kv_len[src]) - lcp \
                    > kc.rows - kc.window - 2:
                return "ring_overwritten"
        return None

    def _count_reuse_skipped(self, reason: str) -> None:
        """A prefix was there to reuse and is prefilled again."""
        self.prefix_reuse_skipped += 1
        self._m_reuse_skipped.inc(1, engine=self.name, reason=reason)

    # -- admission ---------------------------------------------------------
    def _pick_slot(self) -> Optional[int]:
        free = np.flatnonzero(~self.active)
        if len(free) == 0:
            return None
        # least-recently-retired first: the freshest retired caches stay
        # resident longest, which is what multi-turn prefix reuse wants
        return int(free[np.argmin(self._retired_at[free])])

    def _bucket(self, n: int) -> int:
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _sample_host(self, logits: np.ndarray) -> int:
        if self.temperature <= 0.0:
            return int(np.argmax(logits))
        self._key, sub = jax.random.split(self._key)
        return int(sample_logits(jnp.asarray(logits)[None, :], sub,
                                 self.temperature, self.top_k, self.top_p)[0])

    def admit(self, prompt_ids, max_new_tokens: int,
              tenant: str = "default") -> Optional[AdmitResult]:
        """Admit one sequence into a free slot (prefill + first token).
        Returns None when every slot is busy — the caller queues or
        sheds.  Raises ``ValueError`` for a prompt that cannot fit.
        ``tenant`` namespaces every cache surface the sequence touches
        (device radix, host arena, spill tickets) and labels the
        admission/eviction counters."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # room for prompt + every generated token incl. the final
        # sampled-but-never-fed one
        if len(prompt) + max_new + 1 > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)} tokens) + max_new_tokens "
                f"({max_new}) exceeds the engine's max_len "
                f"({self.max_len})")
        slot = self._pick_slot()
        if slot is None:
            return None
        with step_span("engine.admit") as sp:
            res = self._admit_into(slot, prompt, max_new, str(tenant))
            if sp.live:
                sp.set(bucket=res.bucket, prompt_tokens=len(prompt),
                       reused_tokens=res.reused_tokens, path=res.path,
                       padding_rows=res.bucket - (len(prompt)
                                                  - res.reused_tokens),
                       **self._prefill_attention_attrs())
                if self.experts:
                    sp.set(**{k: self._step_experts[k] for k in (
                        "expert_pairs_held", "expert_tiles_active",
                        "expert_tile_rows")})
            return res

    def _admit_into(self, slot: int, prompt: np.ndarray, max_new: int,
                    tenant: str) -> AdmitResult:
        """The admission itself, once a slot is picked: look for reusable
        K/V, prefill the rest, sample the first token, fill the slot."""
        t0 = time.perf_counter()
        with step_span("engine.admit.lookup"):
            # the slot's tenant is set BEFORE any cache lookup:
            # _best_prefix and _register_prefix scope themselves by it
            self._slot_tenant[slot] = tenant
            src, lcp = self._best_prefix(prompt, slot)
            skipped = None if src is None else self._reuse_refused(src, lcp)
            if skipped:
                self._count_reuse_skipped(skipped)
                src, lcp = None, 0
            restored = False
            if self.kv_arena is not None:
                # host tier: a spilled span longer than any device-
                # resident prefix restores instead (device reuse is
                # free-er, so it wins ties); every failure here degrades
                # to the device/cold path below — never a wrong token
                akey, alcp = self.kv_arena.longest_prefix(prompt,
                                                          tenant=tenant)
                alcp = self._clamp_reuse(int(min(alcp, len(prompt) - 1)),
                                         len(prompt))
                if akey is not None and alcp >= self.min_prefix \
                        and alcp > lcp:
                    restored = self._restore_from_arena(akey, alcp, slot,
                                                        tenant=tenant)
                    if restored:
                        src, lcp = None, alcp
            if restored or (src is not None and lcp > 0):
                if not restored and src != slot:
                    with self._program_region("prefix_copy"):
                        self.cache = _copy_prefix_jit(
                            self.cache, src, slot, self._copy_length(lcp))
                # src == slot: in-place resume — the reclaimed slot
                # already holds this conversation's prefix K/V, no copy
                self.prefix_hits += 1
                self.prefix_tokens_reused += lcp
                self._m_reuse.inc(1, engine=self.name)
                self._m_reuse_tok.inc(lcp, engine=self.name)
            else:
                lcp = 0
        with step_span("engine.admit.prefill"):
            # pad, upload, dispatch, and the blocking read of the logits
            tail = prompt[lcp:]
            pb = self._bucket(len(tail))
            padded = np.full(pb, self.pad_id, np.int32)
            padded[:len(tail)] = tail
            with self._program_region(_prefill_program_key(pb)):
                self.cache, last = _prefill_slot_jit(
                    self.model, self.variables, self.cache,
                    jnp.asarray(padded), len(tail), slot, lcp,
                    attention_backend=self.attention_backend)
            logits = self._count_experts(np.asarray(last, np.float32), pb)
            self._account_prefill(pb, lcp, len(tail))
        with step_span("engine.admit.commit"):
            tok = self._sample_host(logits)
            plen = len(prompt)
            self.ctx[slot, :plen] = prompt
            self.ctx[slot, plen] = tok
            self.lengths[slot] = plen + 1
            self.kv_len[slot] = plen
            self.active[slot] = True
            self._epoch[slot] += 1
            self._max_new[slot] = max_new
            self._generated[slot] = 1
            self._slot_totals[slot] = 0
            self._register_prefix(slot, prompt)
            if self._drafter is not None:
                # (re)build the slot's n-gram tables from prompt + first
                # token — a REUSED prefix feeds the table identically
                # (the tables index tokens, which admit always has in
                # full)
                self._spec_k[slot] = self._spec_k0
                self._spec_ewma[slot] = 1.0
                self._drafter.begin(slot, self.ctx[slot], plen + 1)
            self.admissions += 1
            self._m_admit.inc(1, engine=self.name, tenant=tenant)
            self.tokens_generated += 1
            finished, reason = self._finish_reason(slot, tok)
            if finished:
                self._retire(slot, reason)
            self._m_occ.set(self.active_count / self.n_slots,
                            engine=self.name)
            self._mkv.admit_latency.observe(
                time.perf_counter() - t0, engine=self.name,
                path="restore" if restored else "cold")
            return AdmitResult(
                slot, tok, finished, lcp, logits, bucket=pb, reason=reason,
                path="restore" if restored else "reuse" if lcp
                else {None: "cold", "recurrent_state": "cold_recurrent",
                      "ring_overwritten": "cold_ring"}[skipped])

    def _latent_form(self, pb: int, start: int) -> str:
        """The form of latent attention a pass of bucket ``pb`` from
        ``start`` takes (``model.LatentAttention``: ``cold`` from 0, else
        the tail's by :func:`~synapseml_tpu.models.llm.pallas_attn
        .latent_prefill_form`)."""
        return "cold" if start == 0 else MIXERS["latent_attention"].tail_form(
            self.cfg, pb, self.max_len)

    def _prefill_plan(self, pb: int, start: int = 0) -> Tuple[str, Tuple]:
        """How a prefill pass of bucket ``pb`` from ``start`` runs its
        attention: ``(path, ((kind record, tile), ...))``, the tiles those
        the model itself asks :func:`prefill_geometry` for (a ring's keys are
        its rows before the pass and the pass; a latent kind's by its form,
        over the pass's own rows from 0, else over the slot's).  ``path``:
        ``tiled`` where every kind has a tile, ``dense`` where none (always,
        off the kernel backends), else ``mixed``."""
        key = (pb, start == 0 and self._latent_kind is not None)
        plan = self._prefill_plans.get(key)
        if plan is None:
            tiles = () if self.attention_backend == "dense" else tuple(
                (kc, self._pass_geometry(kc, pb, start)) for kc in self._kinds)
            tiled = [geo is not None for _, geo in tiles]
            plan = ("tiled" if tiled and all(tiled) else
                    "mixed" if any(tiled) else "dense", tiles)
            self._prefill_plans[key] = plan
        return plan

    def _pass_geometry(self, kc: _KindCache, pb: int, start: int):
        """The prefill kernel's tile for kind ``kc`` in a pass of bucket
        ``pb`` from ``start``, as the model asks for it (None: plain)."""
        if kc.latent:
            form = self._latent_form(pb, start)
            return MIXERS[kc.kind].geometry(
                self.cfg, "expanded" if form == "cold" else form, pb,
                pb if form == "cold" else kc.rows)
        return prefill_geometry(
            pb, kc.rows + (pb if kc.ring else 0), self.cfg.num_heads,
            kc.kv_heads, kc.d_key, kc.d_value, self.cfg.dtype, kc.window)

    @property
    def _latent_kind(self) -> Optional[_KindCache]:
        """The latent attention kind's record, where the model has one."""
        return next((kc for kc in self._kinds if kc.latent), None)

    def _account_prefill(self, pb: int, start: int, plen: int) -> None:
        """Count one prefill pass by its attention's path (and a latent
        kind's form) and its rows, and keep what its span will say
        (:meth:`_prefill_attention_attrs`)."""
        self._m_prefill_attn.inc(1, engine=self.name,
                                 path=self._prefill_plan(pb, start)[0])
        self._m_prefill_rows.inc(plen, engine=self.name, rows="real")
        self._m_prefill_rows.inc(pb - plen, engine=self.name, rows="padding")
        if self._latent_kind is not None:
            self._m_latent_prefill.inc(1, engine=self.name,
                                       form=self._latent_form(pb, start))
        self._last_prefill = (pb, start, plen)

    def _prefill_attention_attrs(self) -> Dict[str, Any]:
        """``engine.admit``'s account of the last prefill pass: its
        attention's path, the (query block, key block) pairs the kernel
        computed a K/V head over the layers that took it, and what the whole
        bucket would have been: host arithmetic from the pass's start, its
        real tokens, the bucket and the geometry, as ``paged_tiles_live``
        is.  Computed only for a span that is recorded."""
        pb, start, plen = self._last_prefill
        path, tiles = self._prefill_plan(pb, start)
        visited = bucket = 0
        for kc, geo in tiles:
            if geo is None:
                continue
            off = start - kc.rows if kc.ring else None
            # a latent pass from 0 attends over its own rows, from 0
            at = 0 if kc.latent and start == 0 else start
            visited += kc.layers * prefill_key_blocks(
                geo, pb, at, plen, kc.window, off)
            bucket += kc.layers * prefill_key_blocks(
                geo, pb, at, pb, kc.window, off)
        out = {"prefill_attention": path,
               "prefill_key_blocks_visited": visited,
               "prefill_key_blocks_bucket": bucket}
        kc = self._latent_kind
        if kc is not None:
            # rows multiplied out to every head, over the kind's layers: the
            # pass's own from 0, every row of the slot for an expanded tail
            form = self._latent_form(pb, start)
            rows = {"cold": pb, "expanded": kc.rows, "absorbed": 0}[form]
            out.update(latent_prefill_form=form,
                       latent_rows_expanded=kc.layers * rows)
        return out

    def _count_experts(self, out: np.ndarray, tokens: int,
                       whole: bool = False) -> np.ndarray:
        """Split what a program of a model with expert layers returned:
        its last entries are the pass's counts (:func:`_apply`), of a pass
        of ``tokens`` tokens (``whole``: ``out`` is the counts alone).
        -> the tokens or logits alone."""
        if not self.experts:
            return out
        n = len(EXPERT_COUNTS)
        counts = dict(zip(EXPERT_COUNTS, (int(c) for c in out[-n:])))
        pairs = counts["expert_pairs_held"]
        # rows of the tiles computed: each tile is one expert's pairs and
        # the padding that fills it
        rows = counts["expert_tiles_active"] * expert_row_tile(self.cfg,
                                                               tokens)
        self._step_experts = dict(counts, expert_tile_rows=rows)
        self._m_expert_pairs.inc(pairs, engine=self.name)
        self._m_experts_touched.inc(counts["experts_touched"],
                                    engine=self.name)
        self._m_expert_rows.inc(pairs, engine=self.name, rows="pairs")
        self._m_expert_rows.inc(rows - pairs, engine=self.name,
                                rows="padding")
        return out if whole else out[:-n]

    # -- stepping ----------------------------------------------------------
    def _finish_reason(self, slot: int,
                       tok: int) -> Tuple[bool, Optional[str]]:
        if self.eos_id is not None and tok == self.eos_id:
            return True, "eos"
        if self._generated[slot] >= self._max_new[slot]:
            return True, "length"
        return False, None

    def _retire(self, slot: int, reason: str) -> None:
        """The occupant leaves ``slot`` (``reason``: ``eos``, ``length``,
        ``cancelled``, ``preempted``, ``reset``).  ``trace_sink`` gets
        ``retired`` with what its stay added up to: ``tokens`` generated
        (a resumed occupant's count from its first admission on),
        ``steps`` that gave it a token since this admission or resume,
        and under a drafter the ``verify_steps`` among them and the
        positions ``drafted`` for it and ``accepted``."""
        self.active[slot] = False
        self._retired_at[slot] = time.monotonic()
        self.evictions += 1
        self._m_evict.inc(1, engine=self.name, reason=reason,
                          tenant=self._slot_tenant[slot])
        if self.trace_sink is not None:
            steps, verify, drafted, accepted = (
                int(v) for v in self._slot_totals[slot])
            spec = {} if self._drafter is None else {
                "verify_steps": verify, "drafted": drafted,
                "accepted": accepted}
            self.trace_sink(slot, "retired", reason=reason,
                            tokens=int(self._generated[slot]), steps=steps,
                            **spec)
        span = int(self.kv_len[slot])
        if reason != "reset" and span >= self.min_prefix:
            # re-index the slot under its FULL retired context (prompt
            # + generated tokens) so a follow-up turn's longer prompt
            # matches through the generated span, not just the prompt
            self._register_prefix(slot, self.ctx[slot, :span])
            if self.kv_arena is not None:
                self._spill_slot(slot, span,
                                 "preempt" if reason == "preempted"
                                 else "retire")

    def _spill_slot(self, slot: int, span: int, kind: str) -> None:
        """Spill the slot's live K/V span to the host arena.  Never
        breaks retirement: any failure (a donated-then-deleted cache
        after a failed jit, host OOM) is flight-recorded and the spill
        is simply lost — the conversation cold-prefills later."""
        try:
            rows = [{"k": np.asarray(jax.device_get(layer["k"][slot, :span])),
                     "v": np.asarray(jax.device_get(layer["v"][slot, :span]))}
                    for layer in self.cache]
            self.kv_arena.put(self.ctx[slot, :span], rows, kind=kind,
                              tenant=self._slot_tenant[slot])
        except Exception as exc:  # noqa: BLE001 — spill is best-effort
            _flight_record("kvtier_spill_failed", engine=self.name,
                           slot=int(slot), error=repr(exc))

    def _restore_from_arena(self, key: int, span: int, slot: int,
                            tenant: str = "default") -> bool:
        """Restore ``span`` K/V rows of arena entry ``key`` into
        ``slot``.  False on any degraded outcome (checksum failure,
        entry evicted since the probe, a cross-tenant key) — counted,
        flight-recorded, and the caller falls back to cold prefill."""
        try:
            rows = self.kv_arena.fetch(key, span, tenant=tenant)
        except ChecksumError:
            self._mkv.restores.inc(1, engine=self.name, source="host",
                                   outcome="corrupt")
            _flight_record("kvtier_restore_corrupt", engine=self.name,
                           key=int(key), tokens=int(span))
            return False
        except KeyError:
            self._mkv.restores.inc(1, engine=self.name, source="host",
                                   outcome="miss")
            return False
        b = self._bucket(span)
        padded = []
        for r in rows:
            k = np.zeros((b,) + r["k"].shape[1:], r["k"].dtype)
            v = np.zeros((b,) + r["v"].shape[1:], r["v"].dtype)
            k[:span], v[:span] = r["k"], r["v"]
            padded.append({"k": jnp.asarray(k), "v": jnp.asarray(v)})
        with self._program_region(_restore_program_key(b)):
            self.cache = _restore_span_jit(self.cache, padded, slot)
        self._mkv.restores.inc(1, engine=self.name, source="host",
                               outcome="ok")
        return True

    # -- preemption --------------------------------------------------------
    def preempt_slot(self) -> Optional[int]:
        """The lowest-near-term-value ACTIVE slot — the one with the
        most remaining token budget (it frees capacity the longest and
        its progress is cheapest to set aside).  None when idle."""
        if not self.active.any():
            return None
        rem = np.where(self.active, self._max_new - self._generated, -1)
        return int(np.argmax(rem))

    def preempt(self, slot: int) -> Optional[Dict[str, Any]]:
        """Evict an ACTIVE slot mid-decode: spill its K/V to the arena
        (when attached) and return a resume ticket — the full context
        (including the pending sampled-but-unfed token), the valid K/V
        span, and the budget position.  :meth:`resume` continues the
        sequence token-exactly; eviction is just retirement + spill,
        resume is restore + continue (the primitive QoS preemption
        rides)."""
        if not self.active[slot]:
            return None
        ticket = {"ids": self.ctx[slot, :int(self.lengths[slot])].copy(),
                  "kv_len": int(self.kv_len[slot]),
                  "generated": int(self._generated[slot]),
                  "max_new": int(self._max_new[slot]),
                  "tenant": self._slot_tenant[slot]}
        self._retire(slot, "preempted")
        self._m_occ.set(self.active_count / self.n_slots, engine=self.name)
        if self._drafter is not None:
            self._drafter.forget(slot)
        return ticket

    def resume(self, ticket: Dict[str, Any]) -> Optional[int]:
        """Re-admit a preempted ticket into a free slot and continue
        decoding exactly where it left off.  The K/V span is restored
        from the host arena when possible, copied from a device-
        resident prefix otherwise, and cold-prefilled as the last
        resort — all three paths reproduce the identical K/V, so the
        continuation is token-exact regardless.  A model with recurrent
        layers always takes the last: its state after the span was not
        kept (counted in ``llm_prefix_reuse_skipped_total``); so does a
        slot whose window layers are on a ring once the source has written
        past the ring's spare rows (:meth:`_reuse_refused`).  Returns
        the slot, or None when every slot is busy."""
        ids = np.asarray(ticket["ids"], np.int32).reshape(-1)
        span = int(ticket["kv_len"])
        if len(ids) == 0 or span < 1 or span >= len(ids):
            # the pending token ids[span] must exist past the K/V span
            raise ValueError("malformed resume ticket")
        slot = self._pick_slot()
        if slot is None:
            return None
        tenant = str(ticket.get("tenant", "default"))
        self._slot_tenant[slot] = tenant
        est = 0
        if self.kv_arena is not None and span >= self.min_prefix:
            akey, alcp = self.kv_arena.longest_prefix(ids[:span],
                                                      tenant=tenant)
            alcp = self._clamp_reuse(int(min(alcp, span)), span)
            if akey is not None and alcp >= self.min_prefix \
                    and self._restore_from_arena(akey, alcp, slot,
                                                 tenant=tenant):
                est = alcp
        if est == 0:
            radix = self._radices.get(tenant)
            src, dlcp = (radix.longest_prefix(ids[:span], prefer=slot)
                         if radix is not None else (None, 0))
            if src is not None:
                dlcp = self._clamp_reuse(
                    int(min(dlcp, self.kv_len[src], span)), span)
                refused = self._reuse_refused(src, dlcp)
                if dlcp >= self.min_prefix and refused:
                    self._count_reuse_skipped(refused)   # rebuilt from 0
                elif dlcp >= self.min_prefix:
                    if src != slot:
                        with self._program_region("prefix_copy"):
                            self.cache = _copy_prefix_jit(
                                self.cache, src, slot,
                                self._copy_length(dlcp))
                    est = dlcp
        if est < span:
            # cold tail: rebuild K/V for ids[est:span]; the logits are
            # discarded — the pending token (ids[span]) is already
            # sampled and committed, we only need the rows
            tail = ids[est:span]
            pb = self._bucket(len(tail))
            padded = np.full(pb, self.pad_id, np.int32)
            padded[:len(tail)] = tail
            with self._program_region(_prefill_program_key(pb)):
                self.cache, _ = _prefill_slot_jit(
                    self.model, self.variables, self.cache,
                    jnp.asarray(padded), len(tail), slot, est,
                    attention_backend=self.attention_backend)
            self._account_prefill(pb, est, len(tail))
        ln = len(ids)
        self.ctx[slot, :ln] = ids
        self.lengths[slot] = ln
        self.kv_len[slot] = span
        self.active[slot] = True
        self._epoch[slot] += 1
        self._max_new[slot] = int(ticket["max_new"])
        self._generated[slot] = int(ticket["generated"])
        self._slot_totals[slot] = 0
        self._register_prefix(slot, ids[:span])
        if self._drafter is not None:
            self._spec_k[slot] = self._spec_k0
            self._spec_ewma[slot] = 1.0
            self._drafter.begin(slot, self.ctx[slot], ln)
        self._m_occ.set(self.active_count / self.n_slots, engine=self.name)
        return slot

    def cancel(self, slot: int) -> None:
        """Retire ``slot`` early (client gone / reply window expired) —
        frees the slot next step; its K/V stays as prefix material."""
        if self.active[slot]:
            self._retire(slot, "cancelled")
            self._m_occ.set(self.active_count / self.n_slots,
                            engine=self.name)

    def reset(self) -> None:
        """Recover from a failed jitted call.  The decode/prefill
        programs DONATE the cache buffers, so an exception raised
        mid-call can leave ``self.cache`` pointing at deleted arrays —
        every later admit/step would fail forever.  Rebuild the cache
        and clear every slot (active sequences are lost — the serving
        loop answers their 500s and calls this)."""
        for slot in np.flatnonzero(self.active):
            self._retire(int(slot), "reset")
        self._flight = None
        self.cache = init_cache(self.cfg, self.n_slots, self.max_len)
        # all cached K/V died with the old buffers: nothing is a valid
        # prefix source anymore
        self.kv_len[:] = 0
        self.lengths[:] = 0
        self._radices.clear()
        self._slot_radix.clear()
        if self._drafter is not None:
            for slot in range(self.n_slots):
                self._drafter.forget(slot)
            self._spec_k[:] = self._spec_k0
            self._spec_ewma[:] = 1.0
        self._m_occ.set(0.0, engine=self.name)

    def _decode_step_args(self, active: np.ndarray, lengths: np.ndarray):
        """(jit kwargs, spans) for a step that advances ``active`` at
        ``lengths``: the backend and the engine's resolved K/V tile (it
        rides the jit statics so the kernel and the byte ledger can
        never price different geometries), and the per-slot live spans
        the byte ledger prices (an inactive slot's is 1)."""
        lengths = np.where(active, lengths, 1)
        return {"attention_backend": self.attention_backend,
                "paged_tile": self._paged_tile}, lengths

    def _account_decode_bytes(self, spans: np.ndarray, served: int,
                              query_span: int = 1) -> None:
        """Per-step decode-attention K/V read accounting → the
        ``llm_decode_bytes_per_token`` gauge (exact for the paged kernel:
        it fetches a slot's live tiles and nothing else — ``spans``
        covers ALL slots, inactive ones at span 1, because every slot
        fetches at least its first tile; the full-capacity model for
        dense) and the step's tile counts for the ``engine.step`` span.
        ``query_span``: the S of a verify step, whose ``spans`` include its
        S written positions."""
        itemsize = np.dtype(self.cfg.dtype).itemsize
        if self._paged_geo is not None:
            # each attention kind by its own geometry: a window layer's
            # walk starts at its window's first tile, a packed row has its
            # own lanes, a ring is walked by position like any row
            nbytes = live = 0
            self._step_tiles = {}
            for kc in self._kinds:
                nbytes += paged_read_bytes(
                    spans, kc.geo.tile, kc.row_heads, kc.d_key, itemsize,
                    kc.layers, kc.window, query_span, d_value=kc.d_value,
                    pack=kc.pack)
                # the kernel makes one loop trip a tile it fetches: walked
                # over live is 1.0 while no dead tile is walked
                tiles = kc.layers * paged_live_tiles(
                    spans, kc.geo.tile, kc.window, query_span)
                live += tiles
                if kc.latent:       # the latent kernel's walk, of them
                    self._step_tiles["latent_tiles_walked"] = tiles
            self._step_tiles.update(paged_tiles_live=live,
                                    paged_tiles_walked=live)
        else:
            nbytes = sum(dense_read_bytes(
                self.n_slots, kc.rows, kc.kv_heads,
                (kc.d_key + kc.d_value) / 2, itemsize, kc.layers)
                for kc in self._kinds)
        in_use = spans[spans > 1]
        for kc in self._kinds:
            self._m_kv_in_use.set(
                kc.layers * int(np.minimum(in_use, kc.rows).sum())
                * kc.held_row_bytes(itemsize),
                engine=self.name, kind=kc.kind)
        self.decode_attn_bytes += nbytes
        self._m_decode_bytes.set(nbytes / max(1, served),
                                 engine=self.name,
                                 backend=self.attention_backend)

    def step(self) -> List[StepEvent]:
        """One decode step across every active slot.  Returns the
        per-slot events (token + retirement verdicts, possibly SEVERAL
        per slot when a drafted span is accepted); empty when no slot
        is active.  Every call returns one whole step's events, in step
        order.

        Without a drafter the call first hands the device the step
        AFTER the one it returns (where any slot will still be active),
        fed from the returned step's tokens on the device, and then
        reads, commits and returns its own: the host's work runs under
        the next step's program.

        With ``spec_draft_len > 0`` the engine asks the n-gram drafter
        for a span per slot first: any hit upgrades the step to a
        multi-token VERIFY (every slot advances by its accepted span);
        an all-miss step falls back to the plain one-token step — a
        miss costs nothing.  The drafter reads the host's tokens, so
        such an engine dispatches and reads each step in one call."""
        flight = self._flight
        if flight is not None and not self._live(flight).any():
            # cancelled or re-admitted under it: nothing waits on it
            flight = self._flight = None
        if flight is None and not self.active.any():
            return []
        with step_span("engine.step") as sp:
            if sp.live:
                act = self.active if flight is None else self._live(flight)
                sp.set(slots=int(act.sum()),
                       kv_span_sum=int(self.lengths[act].sum()),
                       state_bytes=2 * int(act.sum()) * self.slot_state_bytes,
                       overlapped=flight is not None)
                if self.cfg.num_window_layers:
                    # what a window layer has to read of those spans
                    sp.set(kv_window_span_sum=int(np.minimum(
                        self.lengths[act], self.cfg.sliding_window).sum()))
                itemsize = np.dtype(self.cfg.dtype).itemsize
                for kc in self._kinds:
                    # K and V bytes the kind's layers need this step: each
                    # slot's span (its window's keys at most), unpadded
                    keys = self.lengths[act] if kc.window is None else \
                        np.minimum(self.lengths[act], kc.window)
                    sp.set(**{"kv_bytes_" + kc.kind: kc.layers * int(
                        keys.sum()) * kc.row_bytes(itemsize)})
                    if kc.ring:
                        sp.set(kv_ring_rows=kc.rows)
            events = None
            if self._drafter is not None:
                with step_span("engine.step.draft"):
                    s_cap = self._spec_headroom()
                    drafts = self._collect_drafts(s_cap)
                if drafts:
                    events = self._verify_step(drafts, s_cap)
            if events is None:
                events = self._plain_step()
            if sp.live:
                sp.set(tokens=len(events), program=self.last_program,
                       **self._step_tiles, **self._step_experts)
            return events

    def _finish_step(self, events: List[StepEvent]) -> List[StepEvent]:
        """Common step epilogue: retirement, counters, and the
        per-slot tokens-per-step EWMA (the serving loop's SLO
        projection divides its remaining-token floor by this)."""
        for ev in events:
            if ev.finished:
                self._retire(ev.slot, ev.reason)
        self.steps_run += 1
        slots = len({ev.slot for ev in events})
        tps = len(events) / max(1, slots)
        self._tps_ewma = (tps if self._tps_ewma is None
                          else 0.8 * self._tps_ewma + 0.2 * tps)
        self._m_occ.set(self.active_count / self.n_slots, engine=self.name)
        return events

    def _live(self, flight: _Flight) -> np.ndarray:
        """The slots whose output of ``flight`` still counts: active,
        and held by the occupant they were dispatched for."""
        return flight.slots & self.active & (flight.epoch == self._epoch)

    def _dispatch(self, prev: Optional[_Flight]) -> Optional[_Flight]:
        """Hand the device one one-token step.  ``prev`` is the step
        before it where that one has not been read: its live slots feed
        from its output on the device at their next position, but for
        those that reach their budget in it; every other active slot
        (admitted, resumed or restored since) feeds the host's pending
        token.  None when no slot would be active."""
        with step_span("engine.step.prepare"):
            # host arrays, their uploads and the dispatch (asynchronous:
            # what is timed there is the enqueue)
            idx = np.arange(self.n_slots)
            carried = (np.zeros(self.n_slots, bool) if prev is None
                       else self._live(prev))
            active = self.active & (
                ~carried | (self._generated + 1 < self._max_new))
            if not active.any():
                return None
            kw, lengths = self._decode_step_args(active,
                                                 self.lengths + carried)
            tokens = np.where(active & ~carried,
                              self.ctx[idx, np.maximum(self.lengths - 1, 0)],
                              self.pad_id).astype(np.int32)
            prev_nxt = self._no_prev if prev is None else prev.nxt
            program = _decode_program_key(self.attention_backend)
            with step_span("engine.step.prepare.upload"):
                step_in = (jnp.asarray(tokens),
                           jnp.asarray(lengths.astype(np.int32)),
                           jnp.asarray(active))
                feed_host = jnp.asarray(~carried)
            with self._program_region(program), \
                    step_span("engine.step.prepare.dispatch"):
                self.cache, nxt, self._key = _decode_step_jit(
                    self.model, self.variables, self.cache, *step_in,
                    self._key, self.temperature, self.top_k, self.top_p,
                    prev_nxt=prev_nxt, feed_host=feed_host, **kw)
            return _Flight(nxt, active, self._epoch.copy(), lengths, program)

    def _plain_step(self) -> List[StepEvent]:
        """The one-token step: make sure it is dispatched, dispatch the
        one after it where the engine may (no drafter waits for this
        step's tokens), then read its tokens and make them the engine's
        state."""
        overlapped = self._flight is not None   # only a step ahead waits there
        t0 = time.perf_counter()
        flight = self._flight or self._dispatch(None)
        self._flight = (self._dispatch(flight) if self._drafter is None
                        else None)
        t1 = time.perf_counter()
        with step_span("engine.step.wait"):
            # the step's one blocking call
            nxt = self._count_experts(np.asarray(flight.nxt), self.n_slots)
        t2 = time.perf_counter()
        with step_span("engine.step.commit"):
            self.last_program = flight.program
            if overlapped:
                self.steps_overlapped += 1
                self._m_overlap.inc(1, engine=self.name)
            live = self._live(flight)
            self._account_decode_bytes(flight.lengths, int(live.sum()))
            self._count_step(live.astype(np.int64))
            events: List[StepEvent] = []
            for slot in np.flatnonzero(live):
                slot = int(slot)
                tok = int(nxt[slot])
                ln = int(self.lengths[slot])
                self.ctx[slot, ln] = tok
                self.lengths[slot] = ln + 1
                self.kv_len[slot] = ln    # the fed token's K/V just landed
                self._generated[slot] += 1
                self.tokens_generated += 1
                if self._drafter is not None:
                    self._drafter.extend(slot, self.ctx[slot], ln, ln + 1)
                finished, reason = self._finish_reason(slot, tok)
                events.append(StepEvent(slot, tok, finished, reason))
            events = self._finish_step(events)
            ahead = self._flight
            if ahead is not None and not self._live(ahead).any():
                self._flight = None   # an EOS emptied it: nothing waits on it
        self._count_phases(t0, t1, t2)
        return events

    def _count_step(self, tokens: np.ndarray) -> None:
        """One step in the totals of the slots it gave a token (``tokens``:
        how many, by slot); the first of an occupant's is its ``decode``
        transition."""
        live = tokens > 0
        if self.trace_sink is not None:
            for slot in np.flatnonzero(live & (self._slot_totals[:, 0] == 0)):
                self.trace_sink(int(slot), "decode", tokens=int(tokens[slot]))
        self._slot_totals[live, 0] += 1

    def _count_phases(self, t0: float, t1: float, t2: float) -> None:
        """A step's three parts into :attr:`phase_seconds`: prepare from
        ``t0``, the wait from ``t1``, the commit from ``t2`` to now."""
        sums = self.phase_seconds
        sums["prepare"] += t1 - t0
        sums["wait"] += t2 - t1
        sums["commit"] += time.perf_counter() - t2

    # -- speculative decoding ----------------------------------------------
    def _spec_headroom(self) -> int:
        """Cache headroom for THIS step's verify width: every written
        position must fit ``max_len``, so S cannot exceed
        ``max_len - longest_active_length + 1`` (>= 2 always — admit
        guarantees ``plen + max_new + 1 <= max_len``).  Computed once
        per step and threaded to draft collection AND the verify
        launch so they can never cap at different values."""
        return self.max_len - int(self.lengths[self.active].max()) + 1

    def _collect_drafts(self, s_cap: int) -> Dict[int, np.ndarray]:
        """Ask the drafter for a span per active slot.  A slot's draft
        is capped by its remaining budget (committing past the budget
        is wasted verify work), its ADAPTIVE cap (the acceptance EWMA),
        and the step's cache headroom ``s_cap``."""
        out: Dict[int, np.ndarray] = {}
        hits = misses = 0
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            rem = int(self._max_new[slot] - self._generated[slot])
            k_cap = min(self.spec_draft_len, int(self._spec_k[slot]),
                        rem - 1, s_cap - 1)
            if k_cap < 1:
                continue            # no draft possible: not a miss
            d = self._drafter.draft(slot, self.ctx[slot],
                                    int(self.lengths[slot]), k_cap)
            if len(d):
                out[slot] = d
                hits += 1
            else:
                misses += 1
        self.spec_draft_hits += hits
        self.spec_draft_misses += misses
        if hits:
            self._m_spec_hit.inc(hits, engine=self.name)
        if misses:
            self._m_spec_miss.inc(misses, engine=self.name)
        return out

    def _spec_bucket(self, max_k: int, s_cap: int) -> int:
        """Static S for this verify step: the next power of two
        covering pending + longest draft, shrunk to the cache headroom
        — one compiled verify program per S, O(log(spec_draft_len))
        programs total."""
        s = max(2, _next_pow2(1 + max_k))
        while s > s_cap and s > 2:
            s //= 2
        return s

    def _verify_step(self, drafts: Dict[int, np.ndarray],
                     s_cap: int) -> List[StepEvent]:
        """One multi-token verify step: score every slot's draft span
        against the model in ONE forward, accept the longest
        exact-greedy prefix, commit accepted + 1 tokens through the
        slot_mask-gated scatter (already landed — only COMMITTED
        positions become attendable via ``lengths``/``kv_len``)."""
        t0 = time.perf_counter()
        with step_span("engine.step.prepare"):
            idx = np.arange(self.n_slots)
            S = self._spec_bucket(max(len(d) for d in drafts.values()),
                                  s_cap)
            kw, lengths = self._decode_step_args(self.active, self.lengths)
            tokens = np.full((self.n_slots, S), self.pad_id, np.int32)
            tokens[:, 0] = np.where(
                self.active, self.ctx[idx, np.maximum(self.lengths - 1, 0)],
                self.pad_id)
            klen = np.zeros(self.n_slots, np.int64)
            for slot, d in drafts.items():
                d = d[:S - 1]
                tokens[slot, 1:1 + len(d)] = d
                klen[slot] = len(d)
            self.last_program = _verify_program_key(
                self.attention_backend, S)
            with step_span("engine.step.prepare.upload"):
                step_in = (jnp.asarray(tokens),
                           jnp.asarray(lengths.astype(np.int32)),
                           jnp.asarray(self.active))
            with self._program_region(self.last_program), \
                    step_span("engine.step.prepare.dispatch"):
                self.cache, g = _verify_step_jit(
                    self.model, self.variables, self.cache, *step_in, **kw)
        t1 = time.perf_counter()
        with step_span("engine.step.wait"):
            g = np.asarray(g)         # the step's one blocking call
            if self.experts:
                rows = -(-len(EXPERT_COUNTS) // S)
                self._count_experts(g[-rows:].reshape(-1)[
                    :len(EXPERT_COUNTS)], self.n_slots * S, whole=True)
                g = g[:-rows]
        t2 = time.perf_counter()
        with step_span("engine.step.commit"):
            events = self._finish_step(
                self._commit_verified(tokens, g, klen, lengths, S))
        self._count_phases(t0, t1, t2)
        return events

    def _commit_verified(self, tokens: np.ndarray, g: np.ndarray,
                         klen: np.ndarray, lengths: np.ndarray,
                         S: int) -> List[StepEvent]:
        """Accept each slot's longest exact-greedy draft prefix plus the
        model's bonus token; the host half of a verify step."""
        self.spec_steps += 1
        events: List[StepEvent] = []
        served = 0
        committed = np.zeros(self.n_slots, np.int64)
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            ln = int(self.lengths[slot])
            k_s = int(klen[slot])
            row = g[slot]
            # longest exact-greedy prefix of the draft, then the bonus
            # token the model produced after it (Leviathan-style greedy
            # verification: every committed token IS the argmax token)
            a = 0
            while a < k_s and int(tokens[slot, a + 1]) == int(row[a]):
                a += 1
            commit = row[:a + 1]
            rem = int(self._max_new[slot] - self._generated[slot])
            commit = commit[:rem]
            if self.eos_id is not None:
                eos = np.flatnonzero(commit == self.eos_id)
                if len(eos):
                    commit = commit[:int(eos[0]) + 1]
            c = len(commit)
            self.ctx[slot, ln:ln + c] = commit
            self.lengths[slot] = ln + c
            # positions ln-1 .. ln+c-2 were fed the COMMITTED tokens,
            # so exactly those K/V rows are valid; rejected positions
            # beyond hold junk the next step overwrites before any
            # query can attend it (the prefill-padding invariant)
            self.kv_len[slot] = ln + c - 1
            self._generated[slot] += c
            self.tokens_generated += c
            served += c
            committed[slot] = c
            self._slot_totals[slot, 1:] += (1, k_s, min(a, k_s))
            if k_s:
                self.spec_drafted += k_s
                self.spec_accepted += min(a, k_s)
                self._m_spec_span.observe(c, engine=self.name)
                if self.spec_adapt:
                    self._adapt_slot(slot, min(a, k_s) / k_s)
            if self._drafter is not None:
                self._drafter.extend(slot, self.ctx[slot], ln, ln + c)
            finished, reason = self._finish_reason(slot, int(commit[-1]))
            for j, tok in enumerate(commit):
                last = j == c - 1
                events.append(StepEvent(slot, int(tok),
                                        finished and last,
                                        reason if last else None))
        self._count_step(committed)
        self._account_decode_bytes(lengths + (S - 1), max(1, served), S)
        return events

    def _adapt_slot(self, slot: int, acceptance: float) -> None:
        """Fold one verify outcome into the slot's rolling acceptance
        EWMA and AIMD the slot's draft cap: a FULLY-accepted draft
        doubles the cap (toward ``spec_draft_len``), a draft that lost
        more than half its tokens halves it, and PERSISTENT badness —
        EWMA under 0.2 — collapses straight to the 1-token probe
        instead of paying the halving ladder down.  A slot in
        predictable text climbs to wide verifies in a few steps; a
        slot that left its predictable region stops paying for them
        while still probing cheaply enough to notice recovery."""
        w = 0.3
        e = (1 - w) * self._spec_ewma[slot] + w * acceptance
        self._spec_ewma[slot] = e
        k = int(self._spec_k[slot])
        if e < 0.2:
            self._spec_k[slot] = 1
        elif acceptance >= 1.0:
            self._spec_k[slot] = min(self.spec_draft_len, max(2, 2 * k))
        elif acceptance < 0.5:
            self._spec_k[slot] = max(1, k // 2)

    # -- output ------------------------------------------------------------
    def generated_ids(self, slot: int) -> np.ndarray:
        """The tokens generated so far in ``slot`` (prompt excluded)."""
        start = int(self.lengths[slot] - self._generated[slot])
        return self.ctx[slot, start:int(self.lengths[slot])].copy()

    def run_to_completion(self, max_steps: Optional[int] = None
                          ) -> Dict[int, np.ndarray]:
        """Drive :meth:`step` until every slot retires (static-batch
        semantics / test harness).  Returns {slot: generated ids}."""
        slots = [int(s) for s in np.flatnonzero(self.active)]
        steps = 0
        while self.active.any():
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return {s: self.generated_ids(s) for s in slots}
