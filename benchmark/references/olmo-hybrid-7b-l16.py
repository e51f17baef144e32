"""Plain reference of the Olmo-Hybrid decoder (``config.json`` of
``allenai/Olmo-Hybrid-7B``: ``model_type`` ``olmo_hybrid``; the linear layers
are FLA's ``GatedDeltaNet``, Yang et al., "Gated Delta Networks", configured
by the ``linear_*`` keys as in Qwen3-Next's config).

``layer_types`` says which layers are ``linear_attention`` and which
``full_attention``.  Per token ``t``, per head of ``d_k``, ``d_v``:

    q~, k~, v~ = x W_q, x W_k, x W_v
    q, k, v    = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))   causal, depthwise, 4 taps
    q = l2norm(q) / sqrt(d_k);  k = l2norm(k)
    alpha = exp(-exp(A_log) softplus(x w_a + dt_bias));  beta = 2 sigmoid(x w_b)
    S <- alpha (S - beta k (k^T S)) + beta k v^T
    o  = S^T q
    y  = (rmsnorm_head(o) * silu(x W_g)) W_o

A full-attention layer: RMSNorm with a learned scale over the whole width of
q and of k, no rotary embedding, causal softmax attention.  Both kinds in the
OLMo block order: ``h = x + rmsnorm(mixer(x))``, ``out = h + rmsnorm(mlp(h))``.
What the config does not state (block order, query/key norm, head size, the
gates' initial values, the meaning of a null ``rope_theta``, l2norm's epsilon
1e-6 as FLA has it) is listed under ``assumed`` in the configuration file.

float32 ``jax.numpy`` with ``precision=HIGHEST``: no kernel, no cache, no
bucket, the recurrence a ``lax.scan`` over tokens exactly as written above,
full attention one masked softmax.  It imports nothing of the program; the
weights are made HERE from the seed, bfloat16, and the harness hands the same
arrays to the program.

Controls (``forward(quant=...)``): ``"fp8"`` rounds every matrix product's
two operands to float8_e4m3 (Mistral's control); ``"state_bf16"`` rounds the
recurrent state to bfloat16 after every token, the precision below the
float32 the configuration states for it.
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


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(h=h, H=H, KV=cfg["num_key_value_heads"],
                D=cfg.get("head_dim") or h // H,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"], LH=cfg["linear_num_value_heads"],
                dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
                taps=cfg["linear_conv_kernel_dim"])


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _mat(k, shape, std=INIT_STD):
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)


def _scale(k, n):
    return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def _mlp(ks, h, F):
    return {"w_gate": _mat(ks[0], (h, F)), "w_up": _mat(ks[1], (h, F)),
            "w_down": _mat(ks[2], (F, h)),
            "ln_attn": _scale(ks[3], h), "ln_mlp": _scale(ks[4], h)}


@functools.partial(jax.jit, static_argnames=("h", "H", "KV", "D", "F"))
def _full_layer(key, i, *, h, H, KV, D, F):
    ks = jax.random.split(jax.random.fold_in(key, i), 11)
    return {**_mlp(ks, h, F),
            "wq": _mat(ks[5], (h, H * D)), "wk": _mat(ks[6], (h, KV * D)),
            "wv": _mat(ks[7], (h, KV * D)), "wo": _mat(ks[8], (H * D, h)),
            "q_norm": _scale(ks[9], H * D), "k_norm": _scale(ks[10], KV * D)}


@functools.partial(jax.jit, static_argnames=("h", "LH", "dk", "dv", "taps", "F"))
def _linear_layer(key, i, *, h, LH, dk, dv, taps, F):
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    # the gates: exp(A_log) in (0.05, 0.25), dt_bias in (-1, 1) and a
    # narrower w_a, so that alpha spans about (0.5, 1) over tokens and heads
    # and beta all of (0, 2): a decay of 1 or 0 everywhere would let a broken
    # recurrence pass
    return {**_mlp(ks, h, F),
            "gdn_wq": _mat(ks[5], (h, LH * dk)),
            "gdn_wk": _mat(ks[6], (h, LH * dk)),
            "gdn_wv": _mat(ks[7], (h, LH * dv)),
            "gdn_wg": _mat(ks[8], (h, LH * dv)),
            "gdn_wo": _mat(ks[9], (LH * dv, h)),
            "gdn_wa": _mat(ks[10], (h, LH), INIT_STD / 4),
            "gdn_wb": _mat(ks[11], (h, LH)),
            "gdn_conv": _mat(ks[12], (taps, LH * (2 * dk + dv)), 0.5),
            "gdn_A_log": jnp.log(jax.random.uniform(
                ks[13], (LH,), jnp.float32, 0.05, 0.25)),
            "gdn_dt_bias": jax.random.uniform(ks[14], (LH,), jnp.float32,
                                              -1.0, 1.0),
            "gdn_o_norm": 1.0 + 0.1 * jax.random.normal(ks[15], (dv,),
                                                        jnp.float32)}


@functools.partial(jax.jit, static_argnames=("h", "V"))
def _outer(key, *, h, V):
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _mat(ks[0], (V, h)), "head": _mat(ks[1], (h, V)),
            "ln_final": _scale(ks[2], h)}


def layer_weights(cfg: Dict[str, Any], seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights on the device (matrices and norm scales
    bfloat16, the gates' ``A_log``, ``dt_bias`` and the head norm's scale
    float32), its keys those of its kind in ``layer_types``.  One compiled
    program per kind (``i`` is an operand), so the harness and the reference
    get the same bits."""
    d = dims(cfg)
    i_ = jnp.asarray(i, jnp.int32)
    if cfg["layer_types"][i] == "linear_attention":
        return _linear_layer(seed_key(seed), i_, h=d["h"], LH=d["LH"],
                             dk=d["dk"], dv=d["dv"], taps=d["taps"], F=d["F"])
    return _full_layer(seed_key(seed), i_, h=d["h"], H=d["H"], KV=d["KV"],
                       D=d["D"], F=d["F"])


def outer_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    d = dims(cfg)
    return _outer(seed_key(seed), h=d["h"], V=d["V"])


# -- the lower precisions of the controls -------------------------------------

def _e4m3(x):
    """Round to 4 exponent and 3 mantissa bits.  ``reduce_precision`` and not
    a pair of casts: XLA may drop a cast down and up again as excess
    precision it is allowed to keep."""
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


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
    elif quant not in (None, "state_bf16"):
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the equations -------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, w):
    """x (T, C), w (taps, C): y[t] = sum_j w[j] x[t - (taps-1) + j], zeros
    before the first token; the last tap multiplies the token itself."""
    taps = w.shape[0]
    T = x.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x], 0)
    return sum(xp[j:j + T] * w[j].astype(jnp.float32) for j in range(taps))


def gated_delta_rule(q, k, v, alpha, beta, round_state=False):
    """The recurrence, token by token.  q, k (T, H, d_k), v (T, H, d_v),
    alpha, beta (T, H) -> (o (T, H, d_v), the state after the last token
    (H, d_k, d_v)); the state starts at zero."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(S, xs):
        qt, kt, vt, at, bt = xs
        kS = jnp.einsum("hk,hkv->hv", kt, S, precision=HIGHEST)
        S = at[:, None, None] * (S - bt[:, None, None] * kt[:, :, None]
                                 * kS[:, None, :]) \
            + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        if round_state:
            S = _bf16(S)
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    S, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))
    return o, S


def linear_inputs(x, w, *, LH, dk, dv, neg, quant=None):
    """What the recurrence takes of a layer's input x (T, h): q, k, v,
    alpha, beta."""
    T = x.shape[0]
    mixed = jnp.concatenate([_mm(x, w["gdn_wq"], quant),
                             _mm(x, w["gdn_wk"], quant),
                             _mm(x, w["gdn_wv"], quant)], axis=-1)
    mixed = jax.nn.silu(causal_conv(mixed, w["gdn_conv"]))
    q, k, v = jnp.split(mixed, [LH * dk, 2 * LH * dk], axis=-1)
    q = l2norm(q.reshape(T, LH, dk)) / np.sqrt(dk)
    k = l2norm(k.reshape(T, LH, dk))
    v = v.reshape(T, LH, dv)
    alpha = jnp.exp(-jnp.exp(w["gdn_A_log"]) * jax.nn.softplus(
        _mm(x, w["gdn_wa"], quant) + w["gdn_dt_bias"]))
    beta = (2.0 if neg else 1.0) * jax.nn.sigmoid(_mm(x, w["gdn_wb"], quant))
    return q, k, v, alpha, beta


def linear_mixer(x, w, *, LH, dk, dv, neg, eps, quant):
    o, _ = gated_delta_rule(
        *linear_inputs(x, w, LH=LH, dk=dk, dv=dv, neg=neg, quant=quant),
        round_state=quant == "state_bf16")
    o = rms_norm(o, w["gdn_o_norm"], eps).reshape(x.shape[0], LH * dv)
    return _mm(o * jax.nn.silu(_mm(x, w["gdn_wg"], quant)), w["gdn_wo"], quant)


def full_mixer(x, w, *, H, KV, D, eps, quant):
    T = x.shape[0]
    q = rms_norm(_mm(x, w["wq"], quant), w["q_norm"], eps).reshape(T, H, D)
    k = rms_norm(_mm(x, w["wk"], quant), w["k_norm"], eps).reshape(T, KV, D)
    v = _mm(x, w["wv"], quant).reshape(T, KV, D)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / np.sqrt(D)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST).reshape(T, H * D)
    return _mm(o, w["wo"], quant)


@functools.partial(jax.jit, static_argnames=(
    "kind", "H", "KV", "D", "LH", "dk", "dv", "neg", "eps", "quant"))
def block(x, w, *, kind, H, KV, D, LH, dk, dv, neg, eps, quant=None):
    """One decoder block over one row: x (T, h) float32."""
    if kind == "linear_attention":
        a = linear_mixer(x, w, LH=LH, dk=dk, dv=dv, neg=neg, eps=eps,
                         quant=quant)
    else:
        a = full_mixer(x, w, H=H, KV=KV, D=D, eps=eps, quant=quant)
    x = x + rms_norm(a, w["ln_attn"], eps)
    m = _mm(jax.nn.silu(_mm(x, w["w_gate"], quant)) * _mm(x, w["w_up"], quant),
            w["w_down"], quant)
    return x + rms_norm(m, w["ln_mlp"], eps)


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
    compiled shape); the mask and the recurrence are causal, so the padding
    changes nothing before it.  Returns float32 arrays (len(want[i]), vocab)."""
    d = dims(cfg)
    outer = outer_weights(cfg, seed)
    eps = float(cfg["rms_norm_eps"])
    xs = []
    for ids in rows:
        if len(ids) > pad_to:
            raise ValueError(f"row of {len(ids)} tokens > pad_to={pad_to}")
        padded = np.zeros(pad_to, np.int32)
        padded[:len(ids)] = ids
        xs.append(outer["embed"][jnp.asarray(padded)].astype(jnp.float32))
    for i in range(d["L"]):
        w = layer_weights(cfg, seed, i)
        xs = [block(x, w, kind=cfg["layer_types"][i], H=d["H"], KV=d["KV"],
                    D=d["D"], LH=d["LH"], dk=d["dk"], dv=d["dv"],
                    neg=bool(cfg["linear_allow_neg_eigval"]), eps=eps,
                    quant=quant) for x in xs]
        del w
    return [np.asarray(head(x[jnp.asarray(np.asarray(pos, np.int32))], outer,
                            eps=eps, quant=quant))
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
