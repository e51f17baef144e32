"""Plain reference of the Mistral-7B decoder (Jiang et al. 2023, "Mistral 7B";
``transformers`` ``MistralForCausalLM`` as configured by the model's
``config.json``): pre-norm blocks of RMSNorm, grouped-query attention with
rotary embeddings in the rotate-half arrangement, SwiGLU feed-forward, a
final RMSNorm and an untied output head.  v0.3 has no sliding window.

float32 ``jax.numpy`` with ``precision=HIGHEST``: no kernel, no cache, no
batching, one full causal forward pass over each row of tokens.  It imports
nothing of the program and takes nothing the program made: the weights are
made HERE from the seed (:func:`layer_weights`, :func:`outer_weights`), in
the type the configuration serves them in, and the harness hands the same
arrays to the program.

The control (:func:`forward` with ``quant=``) is this same pass computed one
precision below the configuration's bfloat16: ``"fp8"`` rounds every matrix
product's two operands to float8_e4m3 (weights with one scale per output
column, activations with one per token).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return dict(h=cfg["hidden_size"], H=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("h", "H", "KV", "D", "F"))
def _layer(key, i, *, h, H, KV, D, F):
    ks = jax.random.split(jax.random.fold_in(key, i), 9)

    def mat(k, shape):
        return (INIT_STD * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    def scale(k):
        return (1.0 + 0.1 * jax.random.normal(k, (h,), jnp.float32)
                ).astype(jnp.bfloat16)

    return {"wq": mat(ks[0], (h, H * D)), "wk": mat(ks[1], (h, KV * D)),
            "wv": mat(ks[2], (h, KV * D)), "wo": mat(ks[3], (H * D, h)),
            "w_gate": mat(ks[4], (h, F)), "w_up": mat(ks[5], (h, F)),
            "w_down": mat(ks[6], (F, h)),
            "ln_attn": scale(ks[7]), "ln_mlp": scale(ks[8])}


@functools.partial(jax.jit, static_argnames=("h", "V"))
def _outer(key, *, h, V):
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": (INIT_STD * jax.random.normal(ks[0], (V, h), jnp.float32)
                      ).astype(jnp.bfloat16),
            "head": (INIT_STD * jax.random.normal(ks[1], (h, V), jnp.float32)
                     ).astype(jnp.bfloat16),
            "ln_final": (1.0 + 0.1 * jax.random.normal(ks[2], (h,),
                                                       jnp.float32)
                         ).astype(jnp.bfloat16)}


def layer_weights(cfg: Dict[str, Any], seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights, on the device, bfloat16.  One compiled
    program for every layer (``i`` is an operand), so the harness and the
    reference get the same bits."""
    d = dims(cfg)
    return _layer(seed_key(seed), jnp.asarray(i, jnp.int32), h=d["h"],
                  H=d["H"], KV=d["KV"], D=d["D"], F=d["F"])


def outer_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    d = dims(cfg)
    return _outer(seed_key(seed), h=d["h"], V=d["V"])


# -- the lower precisions of the control --------------------------------------

def _e4m3(x):
    """Round to 4 exponent and 3 mantissa bits.  ``reduce_precision`` and not
    a pair of casts: XLA may drop a cast down and up again as excess
    precision it is allowed to keep."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _fp8_cols(w):          # one scale per output column; 240 is e4m3's largest
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-30) / 240.0
    return _e4m3(w / s) * s


def _fp8_rows(x):          # one scale per token
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 240.0
    return _e4m3(x / s) * s


def _mm(x, w, quant: Optional[str]):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8_rows(x), _fp8_cols(w)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the equations -------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, theta):
    """x (T, heads, D) at positions 0..T-1, rotate-half arrangement."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("H", "KV", "D", "theta", "eps",
                                             "quant"))
def block(x, w, *, H, KV, D, theta, eps, quant=None):
    """One decoder block over one row: x (T, h) float32."""
    T = x.shape[0]
    a = rms_norm(x, w["ln_attn"], eps)
    q = rope(_mm(a, w["wq"], quant).reshape(T, H, D), theta)
    k = rope(_mm(a, w["wk"], quant).reshape(T, KV, D), theta)
    v = _mm(a, w["wv"], quant).reshape(T, KV, D)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / np.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST).reshape(T, H * D)
    x = x + _mm(o, w["wo"], quant)
    m = rms_norm(x, w["ln_mlp"], eps)
    g = _mm(m, w["w_gate"], quant)
    u = _mm(m, w["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, outer, *, eps, quant=None):
    return _mm(rms_norm(x, outer["ln_final"], eps), outer["head"], quant)


def forward(cfg: Dict[str, Any], seed: int, rows: Sequence[np.ndarray],
            want: Sequence[np.ndarray], pad_to: int,
            quant: Optional[str] = None) -> List[np.ndarray]:
    """Logits of each row of token ids at its ``want`` positions.

    Layer by layer, the layer's weights made anew from the seed, every row
    through it in turn, so that one layer's weights and one row's scores
    are all the device holds.  Rows are padded to ``pad_to`` tokens (one
    compiled shape); under the causal mask the padding changes nothing
    before it.  Returns float32 arrays (len(want[i]), vocab)."""
    d = dims(cfg)
    outer = outer_weights(cfg, seed)
    xs = []
    for ids in rows:
        if len(ids) > pad_to:
            raise ValueError(f"row of {len(ids)} tokens > pad_to={pad_to}")
        padded = np.zeros(pad_to, np.int32)
        padded[:len(ids)] = ids
        xs.append(outer["embed"][jnp.asarray(padded)].astype(jnp.float32))
    for i in range(d["L"]):
        w = layer_weights(cfg, seed, i)
        xs = [block(x, w, H=d["H"], KV=d["KV"], D=d["D"],
                    theta=float(cfg["rope_theta"]),
                    eps=float(cfg["rms_norm_eps"]), quant=quant) for x in xs]
        del w
    return [np.asarray(head(x[jnp.asarray(np.asarray(pos, np.int32))], outer,
                            eps=float(cfg["rms_norm_eps"]), quant=quant))
            for x, pos in zip(xs, want)]


def served_gaps(cfg: Dict[str, Any], seed: int, prompts: Sequence[Sequence[int]],
                served: Sequence[Sequence[int]], pad_to: int,
                control: Optional[str] = None) -> Dict[str, Any]:
    """How far each served token's logit lies below the reference's best.

    For request r with prompt p and served tokens o_1..o_n the reference
    runs once over p + o_1..o_{n-1}; its logits at positions len(p)-1 ..
    len(p)+n-2 are what a greedy decoder chooses o_1..o_n from.  With
    ``control`` the same positions are also computed in the lower
    precision, and the gap read is that of the token IT puts first."""
    rows = [np.asarray(list(p) + list(o[:-1]), np.int32)
            for p, o in zip(prompts, served)]
    want = [np.arange(len(p) - 1, len(p) - 1 + len(o))
            for p, o in zip(prompts, served)]
    ref = forward(cfg, seed, rows, want, pad_to)
    low = forward(cfg, seed, rows, want, pad_to, control) if control else None
    gaps, n = [], 0
    for r, (lg, o) in enumerate(zip(ref, served)):
        tok = (np.asarray(o, np.int64) if low is None
               else low[r].argmax(-1))
        gaps.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
        n += len(tok)
    allg = np.concatenate(gaps)
    return {"widest_gap": float(allg.max()), "tokens": n,
            "mismatches": int((allg > 0).sum()),
            "logit_std": float(np.mean([lg.std() for lg in ref]))}
