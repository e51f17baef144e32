"""Plain reference of the Qwen3-Next-80B-A3B language model (``config.json``
of ``Qwen/Qwen3-Next-80B-A3B-Instruct``: ``model_type`` ``qwen3_next``; the
layout of ``transformers``' ``modeling_qwen3_next``), as ONE chip of a
deployment holds it: the routed experts ``experts_first .. experts_first +
num_experts`` of the router's ``router_experts``.

Every norm but the linear layer's head norm is zero-centred,
``zc(z) = z / sqrt(mean(z^2) + eps) * (1 + w)``.  Layer ``i`` is
``full_attention`` where ``(i + 1) % full_attention_interval == 0``, else
``linear_attention``; every layer is an expert layer.  One layer, ``x`` a
token's residual (pre-norm, no bias anywhere):

    h = zc(x)
    linear_attention (gated DeltaNet), nk key heads, nv = r nk value heads:
        in_proj_qkvz(h) viewed (nk, 2 dk + 2 r dv), per key head
            [q (dk) | k (dk) | v (r dv) | z (r dv)]; v, z -> (nv, dv)
        in_proj_ba(h) viewed (nk, 2 r), per key head [b (r) | a (r)] -> (nv,)
        [q | k | v] (flattened) -> silu(causal depthwise conv, taps, no bias)
        beta = sigmoid(b);  alpha = exp(-exp(A_log) softplus(a + dt_bias))
        q = l2norm(q) dk^-0.5, k = l2norm(k) (+1e-6 under the root);
            value head j takes key head floor(j / r)
        S <- alpha (S - beta k (k^T S)) + beta k v^T,  o = S^T q  (per value
            head, S (dk, dv) float32 from zero)
        mix = out_proj( rms(o) * w_n * silu(z) )   (w_n plain, by value head)
    full_attention (gated): q_proj(h) viewed (H, 2 D) -> [q_h | g_h];
        k, v = k_proj(h), v_proj(h) (KV heads); q <- zc_q(q), k <- zc_k(k)
        per head over D; the rotary embedding on dims 0 .. D p - 1 of each
        head (p = partial_rotary_factor, pairs (i, i + D p / 2), theta
        rope_theta), the rest pass; s = q . k / sqrt(D), causal, H / KV
        query heads a K/V head; mix = o_proj(concat_h(softmax v) *
        sigmoid(g))
    x <- x + mix
    h2 = zc(x);  p = softmax(h2 W_r) over router_experts (float32); T = the
        num_experts_per_tok largest; w_e = p_e / sum_T p (norm_topk_prob)
    x <- x + sum_{e in T, e held here} w_e E_e(h2)
           + sigmoid(h2 w_sg) E_shared(h2),  E(h) = W_down (silu(W_gate h) *
           W_up h)

After the last layer ``zc``, logits ``= h W_head``, the head untied.  The
terms of absent experts are left out, as the program leaves them out (nothing
stands in for the chips that would compute them).  With ``experts_first`` 0
and ``num_experts == router_experts`` this is the uncut layer.  What the
config does not state is listed under ``assumed`` in the configuration file;
the model's multi-token-prediction layer (no key of the config) is not part
of it.

float32 ``jax.numpy`` with ``precision=HIGHEST``: no kernel, no cache, no
bucket; the recurrence a plain token loop (``lax.scan``) exactly as written
above; a held expert is applied to every token and weighted by ``w_e`` (zero
where the token did not select it); attention in blocks of queries so that
the scores over 10,240 keys fit.  It imports nothing of the program; the
weights are made HERE from the seed, bfloat16, in the layout above (the fused
``in_proj_qkvz`` and ``in_proj_ba`` by key head), layer by layer and expert by
expert (expert ``e``'s weights depend on ``e`` alone, so every share of a
layer sees the same expert); :func:`layer_weights` hands the harness the same
arrays cut into the program's projections (:func:`program_layout`).

Controls (``forward(quant=...)``): ``"fp8"`` rounds every matrix product's two
operands to float8_e4m3; ``"no_output_gate"`` leaves attention's output
ungated; ``"key_heads_tiled"`` lets value head ``j`` take key head ``j mod
nk`` (the layout fault a reshape in place of a repeat makes).

What ``served_gaps`` compares: the widest gap over the DECIDED tokens, as the
other expert references read it: a token is decided where, in every layer,
every held expert's router logit lies clear of the edge of the token's top
``num_experts_per_tok`` by more than ``ROUTING_MARGIN`` standard deviations
of the token's logits over all ``router_experts`` (the softmax is monotone,
so the edge of the logits is the edge of the probabilities).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
L2_EPS = 1e-6
#: how far every held expert has to lie from the edge of a token's top k for
#: the token to be compared (module docstring), in standard deviations of the
#: token's router logits (PERF.md section 2)
ROUTING_MARGIN = 0.02
#: queries one block of attention takes: (16 heads, 256, 10,240) float32
#: scores are 168 MB
Q_BLOCK = 256
CONTROLS = ("fp8", "no_output_gate", "key_heads_tiled")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    nk, nv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return dict(h=cfg["hidden_size"], H=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                rot=int(cfg["head_dim"] * float(cfg["partial_rotary_factor"])),
                theta=float(cfg["rope_theta"]), nk=nk, nv=nv, r=nv // nk,
                dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
                taps=cfg["linear_conv_kernel_dim"],
                Fe=cfg["moe_intermediate_size"],
                Fs=cfg["shared_expert_intermediate_size"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                E=cfg["router_experts"], held=cfg["num_experts"],
                first=cfg["experts_first"], k=cfg["num_experts_per_tok"],
                every=cfg["full_attention_interval"])


def layer_kind(cfg: Dict[str, Any], i: int) -> str:
    full = (i + 1) % int(cfg["full_attention_interval"]) == 0
    return "full_attention" if full else "linear_attention"


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _mat(k, shape, std=INIT_STD):
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)


def _zc(k, n, centre=0.0):
    """A zero-centred norm's ``w``: near ``centre``, bfloat16."""
    return (centre + 0.1 * jax.random.normal(k, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def _expert(key, h, F):
    """One expert's three matrices from its own key."""
    ks = jax.random.split(key, 3)
    return _mat(ks[0], (h, F)), _mat(ks[1], (h, F)), _mat(ks[2], (F, h))


def _moe(ks, h, Fe, Fs, E, held, first):
    # expert by expert, one in flight: routed expert e from fold_in(e)
    routed = jax.lax.map(
        lambda e: _expert(jax.random.fold_in(ks[0], e), h, Fe),
        first + jnp.arange(held))
    w = {"router": _mat(ks[1], (h, E)), "experts_gate": routed[0],
         "experts_up": routed[1], "experts_down": routed[2],
         "shared_expert_gate": _mat(ks[3], (h, 1)),
         "ln_attn": _zc(ks[4], h), "ln_mlp": _zc(ks[5], h)}
    w["shared_gate"], w["shared_up"], w["shared_down"] = _expert(ks[2], h, Fs)
    return w


@functools.partial(jax.jit, static_argnames=(
    "h", "nk", "nv", "dk", "dv", "taps", "Fe", "Fs", "E", "held"))
def _linear_layer(key, i, first, *, h, nk, nv, dk, dv, taps, Fe, Fs, E, held):
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    r = nv // nk
    # the gates: exp(A_log) in (0.05, 0.25), dt_bias in (-1, 1) and a
    # narrower a-part of in_proj_ba, so that alpha spans about (0.5, 1) over
    # tokens and heads and beta most of (0, 1): a decay of 1 or 0 everywhere
    # would let a broken recurrence pass
    b = _mat(ks[1], (h, nk, r))
    a = _mat(ks[2], (h, nk, r), INIT_STD / 4)
    return {**_moe(ks[8:], h, Fe, Fs, E, held, first),
            "in_proj_qkvz": _mat(ks[0], (h, nk * (2 * dk + 2 * r * dv))),
            "in_proj_ba": jnp.concatenate([b, a], -1).reshape(h, nk * 2 * r),
            "conv": _mat(ks[3], (taps, nk * 2 * dk + nv * dv), 0.5),
            "A_log": jnp.log(jax.random.uniform(ks[4], (nv,), jnp.float32,
                                                0.05, 0.25)),
            "dt_bias": jax.random.uniform(ks[5], (nv,), jnp.float32, -1.0,
                                          1.0),
            "o_norm": 1.0 + 0.1 * jax.random.normal(ks[6], (dv,), jnp.float32),
            "out_proj": _mat(ks[7], (nv * dv, h))}


@functools.partial(jax.jit, static_argnames=(
    "h", "H", "KV", "D", "Fe", "Fs", "E", "held"))
def _full_layer(key, i, first, *, h, H, KV, D, Fe, Fs, E, held):
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    # q_norm and k_norm at 1 + w with w near 1: a head's scores over 10k
    # positions spread by some 4, so that a few keys take most of a query's
    # mass and attention's term is not an average of thousands of values
    return {**_moe(ks[8:], h, Fe, Fs, E, held, first),
            "wq": _mat(ks[0], (h, H * 2 * D)), "wk": _mat(ks[1], (h, KV * D)),
            "wv": _mat(ks[2], (h, KV * D)), "wo": _mat(ks[3], (H * D, h)),
            "q_norm": _zc(ks[4], D, 1.0), "k_norm": _zc(ks[5], D, 1.0)}


@functools.partial(jax.jit, static_argnames=("h", "V"))
def _outer(key, *, h, V):
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _mat(ks[0], (V, h)), "ln_final": _zc(ks[1], h),
            "head": _mat(ks[2], (h, V))}


def hf_layer_weights(cfg: Dict[str, Any], seed: int, i: int
                     ) -> Dict[str, Any]:
    """Layer ``i``'s weights in the model's own layout (module docstring),
    on the device: matrices and norm ``w`` bfloat16, ``A_log``, ``dt_bias``
    and the head norm's scale float32; the held routed experts stacked
    ``(held, ...)``.  One compiled program a kind of layer (``i`` and the
    first held expert are operands)."""
    d = dims(cfg)
    i_, first = jnp.asarray(i, jnp.int32), jnp.asarray(d["first"], jnp.int32)
    moe = dict(Fe=d["Fe"], Fs=d["Fs"], E=d["E"], held=d["held"])
    if layer_kind(cfg, i) == "linear_attention":
        return _linear_layer(seed_key(seed), i_, first, h=d["h"], nk=d["nk"],
                             nv=d["nv"], dk=d["dk"], dv=d["dv"],
                             taps=d["taps"], **moe)
    return _full_layer(seed_key(seed), i_, first, h=d["h"], H=d["H"],
                       KV=d["KV"], D=d["D"], **moe)


def program_layout(cfg: Dict[str, Any], w: Dict[str, Any]) -> Dict[str, Any]:
    """A linear layer's fused projections cut as the program keeps them
    (what a checkpoint loader does): ``in_proj_qkvz`` into q and k by key
    head and v and z by value head, ``in_proj_ba`` into b and a by value
    head, each column block in head order."""
    if "in_proj_qkvz" not in w:
        return w
    d = dims(cfg)
    nk, r, dk, dv = d["nk"], d["r"], d["dk"], d["dv"]
    h = w["in_proj_qkvz"].shape[0]
    qkvz = w["in_proj_qkvz"].reshape(h, nk, 2 * dk + 2 * r * dv)
    ba = w["in_proj_ba"].reshape(h, nk, 2 * r)
    out = {k: v for k, v in w.items() if k not in ("in_proj_qkvz",
                                                   "in_proj_ba")}
    out.update(
        gdn_wq=qkvz[:, :, :dk].reshape(h, nk * dk),
        gdn_wk=qkvz[:, :, dk:2 * dk].reshape(h, nk * dk),
        gdn_wv=qkvz[:, :, 2 * dk:2 * dk + r * dv].reshape(h, nk * r * dv),
        gdn_wg=qkvz[:, :, 2 * dk + r * dv:].reshape(h, nk * r * dv),
        gdn_wb=ba[:, :, :r].reshape(h, nk * r),
        gdn_wa=ba[:, :, r:].reshape(h, nk * r))
    for key in ("conv", "A_log", "dt_bias", "o_norm", "out_proj"):
        out["gdn_" + key] = out.pop(key)
    return out


def layer_weights(cfg: Dict[str, Any], seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights as the harness hands them to the program: the
    arrays of :func:`hf_layer_weights`, the fused projections cut by
    :func:`program_layout`."""
    return program_layout(cfg, hf_layer_weights(cfg, seed, i))


def outer_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    d = dims(cfg)
    return _outer(seed_key(seed), h=d["h"], V=d["V"])


# -- the lower precision of the control ---------------------------------------

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
    elif quant not in (None,) + CONTROLS:
        raise ValueError(f"unknown control {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the equations -------------------------------------------------------------

def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def zc_norm(x, w, eps):
    """The zero-centred norm: ``rms(x) * (1 + w)``."""
    return rms(x, eps) * (1.0 + w.astype(jnp.float32))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def causal_conv(x, w):
    """x (T, C), w (taps, C): y[t] = sum_j w[j] x[t - (taps-1) + j], zeros
    before the first token; the last tap multiplies the token itself."""
    taps, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], 0)
    return sum(xp[j:j + T] * w[j].astype(jnp.float32) for j in range(taps))


def gated_delta_rule(q, k, v, alpha, beta):
    """The recurrence, token by token.  q, k (T, H, d_k), v (T, H, d_v),
    alpha, beta (T, H) -> o (T, H, d_v); the state starts at zero."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(S, xs):
        qt, kt, vt, at, bt = xs
        kS = jnp.einsum("hk,hkv->hv", kt, S, precision=HIGHEST)
        S = at[:, None, None] * (S - bt[:, None, None] * kt[:, :, None]
                                 * kS[:, None, :]) \
            + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    return o


def linear_mixer(x, w, *, nk, nv, dk, dv, eps, quant):
    """Gated DeltaNet over one row x (T, h), the fused layout's own
    equations (module docstring)."""
    T, r = x.shape[0], nv // nk
    qkvz = _mm(x, w["in_proj_qkvz"], quant).reshape(T, nk, 2 * dk + 2 * r * dv)
    ba = _mm(x, w["in_proj_ba"], quant).reshape(T, nk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv].reshape(T, nv, dv)
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, nv, dv)
    b, a = ba[..., :r].reshape(T, nv), ba[..., r:].reshape(T, nv)
    mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], -1)
    mixed = jax.nn.silu(causal_conv(mixed, w["conv"]))
    q, k, v = jnp.split(mixed, [nk * dk, 2 * nk * dk], axis=-1)
    q = l2norm(q.reshape(T, nk, dk)) * dk ** -0.5
    k = l2norm(k.reshape(T, nk, dk))
    if quant == "key_heads_tiled":
        q, k = jnp.tile(q, (1, r, 1)), jnp.tile(k, (1, r, 1))
    else:                               # value head j takes key head j // r
        q, k = jnp.repeat(q, r, axis=1), jnp.repeat(k, r, axis=1)
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(a + w["dt_bias"]))
    o = gated_delta_rule(q, k, v.reshape(T, nv, dv), alpha, beta)
    o = rms(o, eps) * w["o_norm"] * jax.nn.silu(z)
    return _mm(o.reshape(T, nv * dv), w["out_proj"], quant)


def rope_half(x, rot, theta):
    """x (T, heads, D) at positions 0..T-1: dims 0 .. rot - 1 turned as a
    head of ``rot`` dims, pairs (i, i + rot / 2), pair i by
    ``theta^(-2i/rot)`` a position; the rest pass."""
    T = x.shape[0]
    freq = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def full_mixer(x, w, *, H, KV, D, rot, theta, eps, quant):
    """Gated softmax attention over one row x (T, h)."""
    T = x.shape[0]
    qg = _mm(x, w["wq"], quant).reshape(T, H, 2 * D)
    q, g = qg[..., :D], qg[..., D:].reshape(T, H * D)
    q = rope_half(zc_norm(q, w["q_norm"], eps), rot, theta)
    k = _mm(x, w["wk"], quant).reshape(T, KV, D)
    k = rope_half(zc_norm(k, w["k_norm"], eps), rot, theta)
    v = _mm(x, w["wv"], quant).reshape(T, KV, D)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    bq = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(args):
        qb, i = args                                      # (bq, H, D), (bq,)
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) / np.sqrt(D)
        s = jnp.where((jnp.arange(T)[None, :] <= i[:, None])[None], s,
                      -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(T // bq, bq, H, D),
                            jnp.arange(T).reshape(T // bq, bq)))
    o = o.reshape(T, H * D)
    if quant != "no_output_gate":
        o = o * jax.nn.sigmoid(g)
    return _mm(o, w["wo"], quant)


def swiglu(h, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(h, w_gate, quant)) * _mm(h, w_up, quant),
               w_down, quant)


def route(h, w_router, *, k, quant):
    """-> (experts (T, k), weights (T, k), router logits (T, E)): the ``k``
    largest softmax probabilities over all the router's experts, normalised
    over the ``k``."""
    r = _mm(h, w_router, quant)
    top, idx = jax.lax.top_k(jax.nn.softmax(r, axis=-1), k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True), r


def routing_margin(r, *, k, first, held):
    """r (T, E) router logits -> (T,): the least distance of a held
    expert's logit from the edge it would cross to enter or leave the
    token's top ``k`` (the k+1-th largest logit for a selected expert, the
    k-th for any other), in standard deviations of the token's logits."""
    ranked = jnp.sort(r, axis=-1)
    kth, nxt = ranked[:, -k, None], ranked[:, -k - 1, None]
    rh = jax.lax.dynamic_slice_in_dim(r, first, held, axis=1)
    return jnp.min(jnp.where(rh >= kth, rh - nxt, kth - rh), axis=-1) \
        / jnp.std(r, axis=-1)


def experts(h, w, *, k, first, quant):
    """The held routed experts' weighted terms plus the gated shared
    expert's, once.  -> (terms (T, hidden), routing margin (T,))."""
    idx, wt, r = route(h, w["router"], k=k, quant=quant)

    def one(acc, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(idx == e, wt, 0.0), axis=-1)     # (T,)
        return acc + w_e[:, None] * swiglu(h, wg, wu, wd, quant), None

    held = w["experts_gate"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (first + jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    shared = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant) * jax.nn.sigmoid(_mm(h, w["shared_expert_gate"],
                                                quant))
    return routed + shared, routing_margin(r, k=k, first=first, held=held)


@functools.partial(jax.jit, static_argnames=(
    "kind", "H", "KV", "D", "rot", "theta", "nk", "nv", "dk", "dv", "k",
    "eps", "quant"))
def block(x, w, first, *, kind, H, KV, D, rot, theta, nk, nv, dk, dv, k, eps,
          quant=None):
    """One decoder block over one row: x (T, hidden) float32 -> the row
    after the block, and its tokens' routing margin in this layer."""
    h = zc_norm(x, w["ln_attn"], eps)
    if kind == "linear_attention":
        x = x + linear_mixer(h, w, nk=nk, nv=nv, dk=dk, dv=dv, eps=eps,
                             quant=quant)
    else:
        x = x + full_mixer(h, w, H=H, KV=KV, D=D, rot=rot, theta=theta,
                           eps=eps, quant=quant)
    terms, margin = experts(zc_norm(x, w["ln_mlp"], eps), w, k=k, first=first,
                            quant=quant)
    return x + terms, margin


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, outer, *, eps, quant=None):
    return _mm(zc_norm(x, outer["ln_final"], eps), outer["head"], quant)


def forward_margins(cfg: Dict[str, Any], seed: int, rows: Sequence[np.ndarray],
                    want: Sequence[np.ndarray], pad_to: int,
                    quant: Optional[str] = None):
    """Logits of each row of token ids at its ``want`` positions, and the
    least routing margin over the layers at the same positions.

    Layer by layer, the layer's weights made anew from the seed, every row
    through it in turn, so that one layer's weights and one block of one
    row's scores are all the device holds.  Rows are padded to ``pad_to``
    tokens (one compiled shape); the masks and the recurrence are causal, so
    the padding changes nothing before it."""
    d = dims(cfg)
    outer = outer_weights(cfg, seed)
    eps = float(cfg["rms_norm_eps"])
    first = jnp.asarray(d["first"], jnp.int32)
    xs = []
    for ids in rows:
        if len(ids) > pad_to:
            raise ValueError(f"row of {len(ids)} tokens > pad_to={pad_to}")
        padded = np.zeros(pad_to, np.int32)
        padded[:len(ids)] = ids
        xs.append(outer["embed"][jnp.asarray(padded)].astype(jnp.float32))
    margins = [jnp.full(pad_to, jnp.inf, jnp.float32) for _ in rows]
    for i in range(d["L"]):
        w = hf_layer_weights(cfg, seed, i)
        for n, x in enumerate(xs):
            xs[n], m = block(
                x, w, first, kind=layer_kind(cfg, i), H=d["H"], KV=d["KV"],
                D=d["D"], rot=d["rot"], theta=d["theta"], nk=d["nk"],
                nv=d["nv"], dk=d["dk"], dv=d["dv"], k=d["k"], eps=eps,
                quant=quant)
            margins[n] = jnp.minimum(margins[n], m)
        del w
    want = [np.asarray(pos, np.int32) for pos in want]
    return ([np.asarray(head(x[jnp.asarray(pos)], outer, eps=eps, quant=quant))
             for x, pos in zip(xs, want)],
            [np.asarray(m)[pos] for m, pos in zip(margins, want)])


def forward(cfg: Dict[str, Any], seed: int, rows: Sequence[np.ndarray],
            want: Sequence[np.ndarray], pad_to: int,
            quant: Optional[str] = None) -> List[np.ndarray]:
    """The logits of ``forward_margins``."""
    return forward_margins(cfg, seed, rows, want, pad_to, quant)[0]


def served_gaps(cfg: Dict[str, Any], seed: int, prompts: Sequence[Sequence[int]],
                served: Sequence[Sequence[int]], pad_to: int,
                control: Optional[str] = None) -> Dict[str, Any]:
    """How far each served token's logit lies below the reference's best;
    ``widest_gap`` is the largest over the decided tokens (module
    docstring), ``widest_gap_all`` over all of them.

    For request r with prompt p and served tokens o_1..o_n the reference
    runs once over p + o_1..o_{n-1}; its logits at positions len(p)-1 ..
    len(p)+n-2 are what a greedy decoder chooses o_1..o_n from.  With
    ``control`` the same positions are also computed under the control,
    and the gap read is that of the token IT puts first.  ``by_margin``:
    the widest gap among the tokens whose margin lies in each band, the
    reading ``ROUTING_MARGIN`` is chosen from."""
    rows = [np.asarray(list(p) + list(o[:-1]), np.int32)
            for p, o in zip(prompts, served)]
    want = [np.arange(len(p) - 1, len(p) - 1 + len(o))
            for p, o in zip(prompts, served)]
    ref, margins = forward_margins(cfg, seed, rows, want, pad_to)
    low = forward(cfg, seed, rows, want, pad_to, control) if control else None
    gaps = []
    for n, (lg, o) in enumerate(zip(ref, served)):
        tok = (np.asarray(o, np.int64) if low is None
               else low[n].argmax(-1))
        gaps.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
    allg, allm = np.concatenate(gaps), np.concatenate(margins)
    decided = allm > ROUTING_MARGIN
    bands = [0.0, 0.005, 0.01, 0.02, 0.05, 0.1, np.inf]
    by_margin = {f"{lo:g}-{hi:g}": [int(((allm > lo) & (allm <= hi)).sum()),
                                    round(float(allg[(allm > lo) & (allm <= hi)]
                                                .max(initial=0.0)), 4)]
                 for lo, hi in zip(bands[:-1], bands[1:])}
    return {"widest_gap": float(allg[decided].max()) if decided.any()
            else float("nan"),
            "tokens": int(decided.sum()), "tokens_undecided":
            int((~decided).sum()), "widest_gap_all": float(allg.max()),
            "mismatches": int((allg > 0).sum()),
            "logit_std": float(np.mean([lg.std() for lg in ref])),
            "by_margin": by_margin}
