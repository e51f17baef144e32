"""Pallas TPU kernels of the gated delta rule — the recurrence of a
linear-attention layer (Yang et al., "Gated Delta Networks"; the layer
``transformers`` configures with the ``linear_*`` keys).

Per head, with a state ``S`` of ``(d_k, d_v)`` float32, a token does

    S <- alpha * (S - beta * k (k^T S)) + beta * k v^T
    o  = S^T q

(``q``, ``k`` already normalised, ``alpha`` in (0, 1), ``beta`` in (0, 2)),
which as one pass over ``S`` is ``S' = alpha S + k (beta (v - alpha k^T S))^T``:
the state is read once and written once.  Nothing of it can be sliced by
token position — a slot's whole history is these ``d_k x d_v`` numbers — so
the two kernels are the two things serving does with it:

- :func:`gated_delta_decode` — one token for every slot.  Grid over slots,
  a slot's whole state one block, updated IN PLACE (the state operand is
  aliased to the output).  An inactive slot's block is neither read nor
  written: the scalar-prefetched ``visit`` table maps it to the block of the
  active slot before it, which Pallas has in VMEM already, so a retired
  slot's state stays what it was and costs no bytes.
- :func:`gated_delta_prefill` — one slot, a bucket of tokens in chunks of
  :data:`PREFILL_CHUNK`, the state held in VMEM scratch across the chunks.
  Tokens at or after ``plen`` (the bucket's padding) are skipped: chunks
  past it are neither fetched nor computed.  The body walks token by token
  (the chunked WY form would put the same work on the MXU; ``PERF.md`` §7).

**State layout.**  ``d_v`` = 192 is not a multiple of the 128 lanes, and a
``(96, 192)`` tile would occupy ``(96, 256)`` in HBM and in VMEM alike: a
third more bytes on a kernel that is bound by them.  So ``pack`` heads lie
side by side on the lane axis, ``(heads / pack, d_k, pack * d_v)``, with
``pack`` the smallest divisor of ``heads`` that makes the width a multiple
of 128 (2 at 192: ``(15, 96, 384)``); :func:`pack_state` and
:func:`unpack_state` go between this and ``(heads, d_k, d_v)``.  A head's
``k`` has to lie along the sublanes to scale the rows of ``S``, so ``q`` and
``k`` enter transposed, ``(d_k, heads)``, and a head's column is broadcast
along the lanes of its part of the tile.

**Key heads.**  Where a layer has fewer key heads than value heads
(Qwen3-Next: 16 for 32), q and k enter by KEY head, ``(d_k, key heads)``,
and value head ``j`` broadcasts key head ``j // (heads / key heads)``'s
column: the state, v, the gates and the output stay by value head, and no
repeated copy of q and k is made.

Correctness runs in interpret mode on the CPU against :func:`gated_delta_scan`
(``tests/test_llm_gdn.py``); the same tests compile both kernels for the v5e
at the published geometry.  The kernels' byte and operation counts are the
benchmark's (``benchmark/work_gdn.py``); the program counts only what a slot
keeps (:func:`slot_state_bytes`, read by the engine's gauge and step span).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM the kernels may ask for (a v5e core has 128 MiB; the compiler's
#: default scoped limit of 16 MiB is too small for a double-buffered
#: 2.2 MB state block in and out)
_VMEM_LIMIT = 48 * 1024 * 1024

#: tokens per grid step of the prefill kernel
PREFILL_CHUNK = 16


def gdn_pack(heads: int, d_v: int) -> int:
    """Heads side by side on the lane axis: the smallest divisor of
    ``heads`` whose width ``pack * d_v`` is a multiple of 128, else 1."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * d_v) % 128 == 0:
            return g
    return 1


def state_shape(heads: int, d_k: int, d_v: int) -> Tuple[int, int, int]:
    """A slot's packed state: ``(heads / pack, d_k, pack * d_v)``."""
    g = gdn_pack(heads, d_v)
    return (heads // g, d_k, g * d_v)


def pack_state(s: jnp.ndarray, pack: int) -> jnp.ndarray:
    """``(..., heads, d_k, d_v)`` -> ``(..., heads / pack, d_k, pack * d_v)``."""
    *lead, h, dk, dv = s.shape
    s = s.reshape(*lead, h // pack, pack, dk, dv)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // pack, dk, pack * dv)


def unpack_state(s: jnp.ndarray, pack: int) -> jnp.ndarray:
    """The inverse of :func:`pack_state`."""
    *lead, g, dk, w = s.shape
    s = s.reshape(*lead, g, dk, pack, w // pack)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, g * pack, dk, w // pack)


def gdn_geometry(heads: int, d_k: int, d_v: int) -> Optional[int]:
    """The geometry gate of the compiled kernels: the packing they run
    with, or None where they cannot run (a TPU engine then fails at
    construction, it does not fall back: ``resolve_recurrent_backend``).
    ``d_k`` must be a multiple of the 8 float32 sublanes, the packed width
    of the 128 lanes, and the decode kernel's working set (the state block
    in and out, double-buffered, and the prefill's scratch) must fit
    :data:`_VMEM_LIMIT`."""
    g = gdn_pack(heads, d_v)
    if d_k % 8 or (g * d_v) % 128:
        return None
    if 5 * heads * d_k * d_v * 4 + (4 << 20) > _VMEM_LIMIT:
        return None
    return g


def resolve_recurrent_backend(attention_backend: str, heads: int, d_k: int,
                              d_v: int) -> str:
    """The recurrence follows the engine's RESOLVED attention backend:
    ``'paged'`` (a TPU) runs the compiled kernels, ``'interpret'`` the
    same kernels through the Pallas interpreter, ``'dense'`` the plain
    ``lax.scan`` (the CPU path, and what ``'auto'`` resolves to off a TPU).
    A geometry the compiled kernels cannot take is an error here, not a
    silent XLA run on a chip."""
    if attention_backend == "paged" and gdn_geometry(heads, d_k, d_v) is None:
        raise ValueError(
            f"no gated-delta kernel geometry for heads={heads}, d_k={d_k}, "
            f"d_v={d_v}: d_k must be a multiple of 8, some divisor of heads "
            "times d_v a multiple of 128, and a slot's state must fit VMEM; "
            "attention_backend='dense' runs the recurrence as a lax.scan")
    return attention_backend


def slot_state_bytes(heads: int, d_k: int, d_v: int, conv_rows: int,
                     conv_channels: int, conv_itemsize: int = 2) -> int:
    """Bytes one layer keeps for one slot: the float32 state and the
    convolution window."""
    return heads * d_k * d_v * 4 + conv_rows * conv_channels * conv_itemsize


# ---------------------------------------------------------------------------
# the plain recurrence (the CPU path, and what the kernels are tested against)
# ---------------------------------------------------------------------------

def gated_delta_scan(q, k, v, alpha, beta, state, valid=None):
    """The recurrence as a ``lax.scan`` over tokens.

    ``q``, ``k`` ``(B, S, H, d_k)``, ``v`` ``(B, S, H, d_v)``, ``alpha``,
    ``beta`` ``(B, S, H)``, ``state`` ``(B, H, d_k, d_v)``, all float32;
    ``valid`` ``(B, S)`` bool: a token that is not valid leaves the state
    as it was (its output row is computed from the state unchanged and
    means nothing).  -> ``(o (B, S, H, d_v), state)``."""
    B, S = q.shape[:2]
    if valid is None:
        valid = jnp.ones((B, S), bool)

    def step(s, xs):
        qt, kt, vt, at, bt, ok = xs
        ks = jnp.einsum("bhk,bhkv->bhv", kt, s)
        u = bt[..., None] * (vt - at[..., None] * ks)
        new = at[..., None, None] * s + kt[..., :, None] * u[..., None, :]
        s = jnp.where(ok[:, None, None, None], new, s)
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta, valid))
    state, o = lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _token_update(s_read, s_write, o_write, qT, kT, v, a, b, *, groups: int,
                  pack: int, d_k: int, d_v: int, ratio: int = 1) -> None:
    """One token through every head group of one slot.  ``qT``/``kT``
    ``(d_k, key heads)``, value head ``j`` reading key head ``j // ratio``;
    ``v``, ``a``, ``b`` ``(groups, width)`` (the gates repeated over each
    head's lanes); ``s_read(g)``/``s_write(g, S)`` move a group's ``(d_k,
    width)`` tile, ``o_write(g, row)`` its ``(1, width)`` output."""
    width = pack * d_v
    lane = lax.broadcasted_iota(jnp.int32, (d_k, width), 1)

    def spread(colsT, g):
        # head e of the group owns lanes [e*d_v, (e+1)*d_v): its key head's
        # column, d_k down the sublanes, broadcast along them
        def col(e):
            c = (g * pack + e) // ratio
            return colsT[:, c:c + 1]
        out = jnp.broadcast_to(col(0), (d_k, width))
        for e in range(1, pack):
            c = col(e)
            out = jnp.where(lane >= e * d_v, c, out)
        return out

    for g in range(groups):
        S = s_read(g)
        K = spread(kT, g)
        ag, bg, vg = a[g:g + 1, :], b[g:g + 1, :], v[g:g + 1, :]
        kS = jnp.sum(K * S, axis=0, keepdims=True)
        S = ag * S + K * (bg * (vg - ag * kS))
        s_write(g, S)
        o_write(g, jnp.sum(spread(qT, g) * S, axis=0, keepdims=True))


def _decode_kernel(groups: int, pack: int, d_k: int, d_v: int, ratio: int):
    def kernel(visit_ref, act_ref, s_ref, qT_ref, kT_ref, v_ref, a_ref,
               b_ref, so_ref, o_ref):
        i = pl.program_id(0)
        active = act_ref[i] != 0

        @pl.when(active)
        def _step():
            def s_write(g, S):
                so_ref[0, g] = S

            def o_write(g, row):
                o_ref[0, g:g + 1, :] = row
            _token_update(lambda g: s_ref[0, g], s_write, o_write,
                          qT_ref[0], kT_ref[0], v_ref[0], a_ref[0], b_ref[0],
                          groups=groups, pack=pack, d_k=d_k, d_v=d_v,
                          ratio=ratio)

        @pl.when(jnp.logical_not(active))
        def _idle():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(jnp.logical_not(active) & (i == 0))
        def _carry():
            # the block this step visits is written back whatever the
            # kernel did: where slot 0 is inactive the block (the first
            # active slot's, or slot 0's own when none is) goes through
            # unchanged until its owner's step rewrites it
            so_ref[...] = s_ref[...]
    return kernel


@functools.partial(jax.jit, static_argnames=("pack", "interpret"))
def gated_delta_decode(state: jnp.ndarray,    # (N, groups, d_k, width) f32
                       q: jnp.ndarray,        # (N, Hk, d_k) f32
                       k: jnp.ndarray,        # (N, Hk, d_k) f32
                       v: jnp.ndarray,        # (N, H, d_v) f32
                       alpha: jnp.ndarray,    # (N, H) f32
                       beta: jnp.ndarray,     # (N, H) f32
                       active: jnp.ndarray,   # (N,) bool
                       pack: int,
                       interpret: bool = False):
    """One token for every active slot: -> ``(state, o (N, H, d_v) f32)``,
    the state updated in place.  An inactive slot's state is untouched and
    its output row is zero.  ``q`` and ``k`` by key head: value head ``j``
    reads key head ``j // (H / Hk)``."""
    N, Hk, d_k = q.shape
    H, d_v = v.shape[1:]
    groups, width = H // pack, pack * d_v
    assert state.shape == (N, groups, d_k, width), (state.shape, q.shape)
    act = active.astype(jnp.int32)
    idx = jnp.arange(N, dtype=jnp.int32)
    # the block each grid step visits: its own where active, else the
    # active slot before it (already in VMEM: no DMA), else the first
    # active slot (fetched early, rewritten at its own step), else 0
    before = lax.cummax(jnp.where(active, idx, -1))
    visit = jnp.where(before >= 0, before,
                      jnp.argmax(active)).astype(jnp.int32)

    def rows(x):          # (N, H, d_v) | (N, H) -> (N, groups, width)
        if x.ndim == 2:
            x = jnp.repeat(x[..., None], d_v, axis=-1)
        return x.reshape(N, groups, width)

    st = lambda i, visit, act: (visit[i], 0, 0, 0)      # noqa: E731
    own = lambda i, visit, act: (i, 0, 0)               # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(N,),
        in_specs=[pl.BlockSpec((1, groups, d_k, width), st),
                  pl.BlockSpec((1, d_k, Hk), own),
                  pl.BlockSpec((1, d_k, Hk), own),
                  pl.BlockSpec((1, groups, width), own),
                  pl.BlockSpec((1, groups, width), own),
                  pl.BlockSpec((1, groups, width), own)],
        out_specs=[pl.BlockSpec((1, groups, d_k, width), st),
                   pl.BlockSpec((1, groups, width), own)])
    new_state, o = pl.pallas_call(
        _decode_kernel(groups, pack, d_k, d_v, H // Hk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((N, groups, width), jnp.float32)],
        input_output_aliases={2: 0},          # the state, past the 2 scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gated_delta_decode",
        interpret=interpret,
    )(visit, act, state, jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
      rows(v), rows(alpha), rows(beta))
    return new_state, o.reshape(N, H, d_v)


def _prefill_kernel(groups: int, pack: int, d_k: int, d_v: int, chunk: int,
                    ratio: int):
    def kernel(plen_ref, s0_ref, qT_ref, kT_ref, v_ref, a_ref, b_ref,
               so_ref, o_ref, s_scr):
        c = pl.program_id(0)
        left = plen_ref[0] - c * chunk        # real tokens from this chunk on

        @pl.when(c == 0)
        def _load():
            s_scr[...] = s0_ref[...]

        # padding rows of the output are zero, not whatever the buffer held
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(left > 0)
        def _chunk():
            def token(t, carry):
                def s_write(g, S):
                    s_scr[g] = S

                def o_write(g, row):
                    o_ref[t, g:g + 1, :] = row
                _token_update(lambda g: s_scr[g], s_write, o_write,
                              qT_ref[t], kT_ref[t], v_ref[t], a_ref[t],
                              b_ref[t], groups=groups, pack=pack, d_k=d_k,
                              d_v=d_v, ratio=ratio)
                return carry
            lax.fori_loop(0, jnp.minimum(left, chunk), token, 0)

        @pl.when(c == pl.num_programs(0) - 1)
        def _store():
            so_ref[...] = s_scr[...]
    return kernel


@functools.partial(jax.jit, static_argnames=("pack", "interpret"))
def gated_delta_prefill(state: jnp.ndarray,   # (groups, d_k, width) f32
                        q: jnp.ndarray,       # (T, Hk, d_k) f32
                        k: jnp.ndarray,       # (T, Hk, d_k) f32
                        v: jnp.ndarray,       # (T, H, d_v) f32
                        alpha: jnp.ndarray,   # (T, H) f32
                        beta: jnp.ndarray,    # (T, H) f32
                        plen: jnp.ndarray,    # () int32: real tokens
                        pack: int,
                        interpret: bool = False):
    """One slot's bucket of ``T`` tokens, of which the first ``plen`` are
    real: -> ``(state after token plen-1, o (T, H, d_v) f32)``; the output
    rows at or after ``plen`` are zero.  ``q`` and ``k`` by key head, as
    :func:`gated_delta_decode` takes them."""
    T, Hk, d_k = q.shape
    H, d_v = v.shape[1:]
    groups, width = H // pack, pack * d_v
    assert state.shape == (groups, d_k, width), (state.shape, q.shape)
    chunk = PREFILL_CHUNK if T % PREFILL_CHUNK == 0 else T
    plen = jnp.asarray(plen, jnp.int32).reshape(1)

    def rows(x):
        if x.ndim == 2:
            x = jnp.repeat(x[..., None], d_v, axis=-1)
        return x.reshape(T, groups, width)

    def live(c, plen):
        # a chunk past the real tokens repeats the last live chunk's
        # block: nothing is fetched for it
        return (jnp.minimum(c, jnp.maximum(plen[0] - 1, 0) // chunk), 0, 0)
    whole = lambda c, plen: (0, 0, 0)                   # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(T // chunk,),
        in_specs=[pl.BlockSpec((groups, d_k, width), whole),
                  pl.BlockSpec((chunk, d_k, Hk), live),
                  pl.BlockSpec((chunk, d_k, Hk), live),
                  pl.BlockSpec((chunk, groups, width), live),
                  pl.BlockSpec((chunk, groups, width), live),
                  pl.BlockSpec((chunk, groups, width), live)],
        out_specs=[pl.BlockSpec((groups, d_k, width), whole),
                   pl.BlockSpec((chunk, groups, width),
                                lambda c, plen: (c, 0, 0))],
        scratch_shapes=[pltpu.VMEM((groups, d_k, width), jnp.float32)])
    new_state, o = pl.pallas_call(
        _prefill_kernel(groups, pack, d_k, d_v, chunk, H // Hk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((T, groups, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gated_delta_prefill",
        interpret=interpret,
    )(plen, state, jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
      rows(v), rows(alpha), rows(beta))
    return new_state, o.reshape(T, H, d_v)
