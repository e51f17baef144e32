"""Plain reference of BERT fine-tuning for sequence classification (Devlin et
al. 2019, "BERT", section 4; the post-norm encoder of Vaswani et al. 2017):
token plus position embeddings, LayerNorm, ``L`` blocks of multi-head
self-attention and a GELU feed-forward each followed by residual and
LayerNorm, a tanh pooler over the first token, a linear classifier,
softmax cross-entropy averaged over the batch, and AdamW (Loshchilov &
Hutter 2019: decoupled weight decay on every parameter, bias-corrected
moments, constant learning rate).

Departures from the paper, which follow what the configuration states: no
token-type embedding (single-segment inputs), the tanh approximation of
GELU, LayerNorm epsilon 1e-6, dropout 0 (no two random streams agree, and
the comparison needs every row's gradient).

float32 ``jax.numpy``, matrix products at ``precision=HIGHEST``, gradients by
``jax.grad`` over blocks of rows so that one block's activations are all the
device holds.  The parameters and the batches are made HERE from the seed and
handed to the program; nothing of the program is imported or read.

The control (``quant="fp8"``) rounds both operands of every matrix product to
float8_e4m3 (one scale per output column of a weight, one per row of an
activation), forward and backward, one precision below the bfloat16 the
configuration computes in.  The faults a training cell can have are variants
of :func:`train`: ``half_batch`` (the second half of the rows left out, the
mean taken over the rest) and ``no_exchange`` (one chip's rows alone, as when
the gradient exchange is left out).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
B1, B2, ADAM_EPS = 0.9, 0.999, 1e-8
LN_EPS = 1e-6


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    s = {"tok_embed": (cfg["vocab_size"], h), "pos_embed": (cfg["max_len"], h),
         "ln_embed/scale": (h,), "ln_embed/bias": (h,),
         "pooler/kernel": (h, h), "pooler/bias": (h,),
         "classifier/kernel": (h, cfg["num_labels"]),
         "classifier/bias": (cfg["num_labels"],)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer_{i}/"
        for n in ("query", "key", "value", "out"):
            s[p + n + "/kernel"], s[p + n + "/bias"] = (h, h), (h,)
        s[p + "ffn_up/kernel"], s[p + "ffn_up/bias"] = (h, f), (f,)
        s[p + "ffn_down/kernel"], s[p + "ffn_down/bias"] = (f, h), (h,)
        for n in ("ln_att", "ln_ffn"):
            s[p + n + "/scale"], s[p + n + "/bias"] = (h,), (h,)
    return s


def init_params(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every parameter from the seed, float32, on the device, in one jitted
    call: matrices and embeddings normal(0.02), norm scales 1 + 0.1 normal,
    biases normal(0.02) (none left at zero, so that every leaf moves)."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            x = jax.random.normal(k, shapes[name], jnp.float32)
            out[name] = 1.0 + 0.1 * x if name.endswith("/scale") else 0.02 * x
        return out
    return make(seed_key(seed))


def make_batches(cfg: Dict[str, Any], seed: int, n: int
                 ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """``n`` batches of (token ids (B, S) int32, labels (B,) int32); every
    row differs; a row's label is a rule of its tokens, so the loss can
    fall."""
    rng = np.random.default_rng([int(seed), 21])
    B, S = cfg["global_batch"], cfg["sequence_length"]
    out = []
    for _ in range(n):
        ids = rng.integers(0, cfg["vocab_size"], (B, S), dtype=np.int32)
        labels = ((ids[:, 1] + ids[:, 2]) % cfg["num_labels"]).astype(np.int32)
        out.append((ids, labels))
    return out


# -- the lower precision of the control ---------------------------------------

def _fp8(x, axis):
    """4 exponent and 3 mantissa bits under one scale along ``axis`` (240 is
    the format's largest).  ``reduce_precision`` and not a pair of casts,
    which XLA may drop as excess precision it is allowed to keep."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm(x, w, quant):
    return _mm_fwd(x, w, quant)[0]


def _mm_fwd(x, w, quant):
    if quant == "fp8":
        y = jnp.matmul(_fp8(x, -1), _fp8(w, 0), precision=HIGHEST)
    elif quant is None:
        y = jnp.matmul(x, w, precision=HIGHEST)
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    return y, (x, w)


def _mm_bwd(quant, res, g):
    x, w = res
    x2, g2 = x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1])
    if quant == "fp8":
        dx = jnp.matmul(_fp8(g, -1), _fp8(w, 0).T, precision=HIGHEST)
        dw = jnp.matmul(_fp8(x2, -1).T, _fp8(g2, -1), precision=HIGHEST)
    else:
        dx = jnp.matmul(g, w.T, precision=HIGHEST)
        dw = jnp.matmul(x2.T, g2, precision=HIGHEST)
    return dx, dw


_mm.defvjp(_mm_fwd, _mm_bwd)


# -- the equations --------------------------------------------------------------

def layer_norm(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def logits_of(p: Dict[str, Any], ids, cfg: Dict[str, Any], quant=None):
    L, H = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    B, S = ids.shape
    h = cfg["hidden_size"]
    D = h // H

    def dense(x, name):
        return _mm(x, p[name + "/kernel"], quant) + p[name + "/bias"]

    x = p["tok_embed"][ids] + p["pos_embed"][jnp.arange(S)][None]
    x = layer_norm(x, p["ln_embed/scale"], p["ln_embed/bias"])
    for i in range(L):
        pre = f"layer_{i}/"
        q = dense(x, pre + "query").reshape(B, S, H, D)
        k = dense(x, pre + "key").reshape(B, S, H, D)
        v = dense(x, pre + "value").reshape(B, S, H, D)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(D)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST).reshape(B, S, h)
        x = layer_norm(x + dense(a, pre + "out"),
                       p[pre + "ln_att/scale"], p[pre + "ln_att/bias"])
        f = dense(gelu_tanh(dense(x, pre + "ffn_up")), pre + "ffn_down")
        x = layer_norm(x + f, p[pre + "ln_ffn/scale"], p[pre + "ln_ffn/bias"])
    pooled = jnp.tanh(dense(x[:, 0, :], "pooler"))
    return dense(pooled, "classifier")


def _block_loss_sum(p, ids, labels, cfg_items, quant):
    cfg = dict(cfg_items)
    lg = logits_of(p, ids, cfg, quant)
    lse = jax.nn.logsumexp(lg, -1)
    return jnp.sum(lse - jnp.take_along_axis(lg, labels[:, None], 1)[:, 0])


_grad_block = jax.jit(jax.value_and_grad(_block_loss_sum),
                      static_argnames=("cfg_items", "quant"))


def loss_and_grads(p, ids: np.ndarray, labels: np.ndarray, cfg: Dict[str, Any],
                   quant=None, block: int = 32):
    """Mean loss over the rows given and its gradient, in blocks of rows."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float))))
    n = len(ids)
    total, grads = 0.0, None
    for a in range(0, n, block):
        ls, g = _grad_block(p, jnp.asarray(ids[a:a + block]),
                            jnp.asarray(labels[a:a + block]),
                            cfg_items=items, quant=quant)
        total += float(ls)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / n, jax.tree.map(lambda g: g / n, grads)


@jax.jit
def _adamw(p, g, mu, nu, t, lr, wd):
    mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    p = jax.tree.map(lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2)
                                                           + ADAM_EPS) + wd * w),
                     p, mu, nu)
    return p, mu, nu


def leaf_norms(tree: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train(cfg: Dict[str, Any], seed: int, batches, steps: int = 3,
          quant: Optional[str] = None, variant: Optional[str] = None,
          chips: int = 1) -> Dict[str, Any]:
    """Follow the first ``steps`` steps from the seed's parameters: each
    step's loss (before its update), the norm of each leaf of the first
    gradient, and the norm of each leaf's change after the last step."""
    p0 = init_params(cfg, seed)
    p = p0
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    for t in range(1, steps + 1):
        ids, labels = batches[t - 1]
        if variant == "half_batch":
            ids, labels = ids[:len(ids) // 2], labels[:len(labels) // 2]
        elif variant == "no_exchange":
            ids, labels = ids[:len(ids) // chips], labels[:len(labels) // chips]
        elif variant is not None:
            raise ValueError(f"unknown variant {variant!r}")
        loss, g = loss_and_grads(p, ids, labels, cfg, quant)
        losses.append(loss)
        if first is None:
            first = leaf_norms(g)
        p, mu, nu = _adamw(p, g, mu, nu, float(t), cfg["learning_rate"],
                           cfg["weight_decay"])
    change = leaf_norms(jax.tree.map(jnp.subtract, p, p0))
    return {"losses": losses, "first_grad_norm": first, "change_norm": change}


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers compared.  Losses: the relative gap of the first step's
    loss (the forward pass alone, steady from seed to seed) and the widest
    of the three steps' (the later two carry the noise of the updates before
    them).  Norms, by the worst leaf: the gap between the program's
    norm and the reference's, against the reference's norm of that leaf or
    of the median leaf, whichever is larger.  Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out of the
    change: under Adam they move by round-off alone."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    names = sorted(ref["first_grad_norm"])
    g_ref = np.array([ref["first_grad_norm"][n] for n in names])
    g_prog = np.array([prog["first_grad_norm"][n] for n in names])
    g_gap = np.abs(g_prog - g_ref) / np.maximum(g_ref, np.median(g_ref))
    keep = g_ref >= 1e-3 * np.median(g_ref)
    c_ref = np.array([ref["change_norm"][n] for n in names])
    c_prog = np.array([prog["change_norm"][n] for n in names])
    c_gap = np.abs(c_prog - c_ref) / np.maximum(c_ref, np.median(c_ref[keep]))
    c_gap = np.where(keep, c_gap, 0.0)
    return {"first_loss_gap": abs(prog["losses"][0] - ref["losses"][0])
            / abs(ref["losses"][0]),
            "loss_gap": float(loss_gap),
            "first_grad_norm_gap": float(g_gap.max()),
            "worst_grad_leaf": names[int(g_gap.argmax())],
            "param_change_gap": float(c_gap.max()),
            "worst_change_leaf": names[int(c_gap.argmax())],
            "leaves_left_out": [n for n, k in zip(names, keep) if not k]}
