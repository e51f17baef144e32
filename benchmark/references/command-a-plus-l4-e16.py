"""Plain reference of the Command A+ decoder's language model
(``config.json`` of ``CohereLabs/command-a-plus-05-2026``: ``model_type``
``cohere2_moe``), as ONE chip of a deployment holds it: the routed experts
``experts_first .. experts_first + num_experts`` of the router's
``router_experts``.

One layer, ``x`` a token's residual (``layer_types`` says which layers are
``sliding_attention`` and which ``full_attention``):

    h = layernorm(x) = (x - mean) / sqrt(var + eps) * g             no bias
    q, k, v = h Wq, h Wk, h Wv        H heads of D over KV heads, no bias
    sliding: rotary on all D dims, pairs (2i, 2i+1) interleaved, theta;
             key j visible to query i iff i - window < j <= i
    full:    no positional embedding; key j visible iff j <= i
    a = softmax(q k^T / sqrt(D)) v Wo
    r = h Wr (router_experts logits),  s = sigmoid(r)
    T = the num_experts_per_tok largest s,  w_e = s_e / sum_{T} s
    routed = sum_{e in T, e held here} w_e E_e(h)
    E(h) = Wdown (silu(Wgate h) * Wup h)
    shared = 1/ns sum_s E_s(h)        every token, averaged
    out = x + a + routed + shared     (parallel block: both from the same h)

After the last layer ``layernorm``, logits ``= logit_scale * h E^T`` with
the embedding tied.  ``w_e`` is normalised over all the selected experts,
held here or not; the terms of absent experts are left out, as the program
leaves them out (nothing stands in for the chips that would compute them).
With ``experts_first`` 0 and ``num_experts == router_experts`` this is the
uncut layer.  What the config does not state is listed under ``assumed`` in
the configuration file.

float32 ``jax.numpy`` with ``precision=HIGHEST``: no kernel, no cache, no
bucket, no grouping; a held expert is applied to every token and weighted
by ``w_e`` (zero where the token did not select it); attention in blocks of
queries so that the scores of 128 heads fit.  It imports nothing of the
program; the weights are made HERE from the seed, bfloat16, layer by layer
and expert by expert (expert ``e``'s weights depend on ``e`` alone, so every
share of a layer sees the same expert), and the harness hands the same
arrays to the program.

Controls (``forward(quant=...)``): ``"fp8"`` rounds every matrix product's
two operands to float8_e4m3 (Mistral's control); ``"no_window"`` lets the
sliding layers attend to every earlier key.

What ``served_gaps`` compares.  A choice of 8 among 128 turns on small gaps:
where a held expert's router logit lies within a rounding of the edge of
the token's top 8, a bfloat16 run and this one may select differently, and
the token's logits then differ by a whole expert's term, in ANY bfloat16
run, sound or not.  Such a token says nothing about the precision of the
program, so the widest gap is read over the DECIDED tokens only: those
where, in every layer, every held expert is inside or outside the token's
top 8 by more than ``ROUTING_MARGIN`` of the spread of the token's router
logits, as this reference computes it.  The set is fixed by the reference
alone, so the program and a control are read over the same tokens; on a
decided token a pair that is dropped or sent to the wrong expert moves the
logits by the expert's full term.  Whether the held experts' terms are
exact under any routing is held by ``tests/test_llm_moe.py``, not by
``correct``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
# o_proj's std is the configuration's ``o_proj_init_std`` (default: the
# others').  Its inputs are the heads' outputs, at the published widths
# 16,384 (128 heads of 128), four times the hidden size; at 0.02 attention's
# output, which behind a shared preamble is nearly the same for every
# request, swamps the token's own path: all the greedy streams of a batch
# then emit one and the same token and select the same experts, which no
# trained router does.  At a test's widths (hidden 64) the feed-forward
# terms are small and attention at 0.02 is what makes a token's successor
# depend on its context at all, so the value belongs to the configuration

#: how far every held expert's router logit has to lie from the edge of a
#: token's top k for the token to be compared (module docstring), in standard
#: deviations of the token's router logits.  The program's router runs in
#: float32 on a bfloat16 residual.  On the chip (ten seeds, 12,079 tokens;
#: PERF.md section 2) 95 tokens' served logit fell 0.15 or more under the
#: reference's best, all of them within 0.025 of an edge: 7.6% of the tokens
#: within 0.005, 0.8% of those between 0.01 and 0.02, one of 3,260 between
#: 0.02 and 0.05 (further than a rounding reaches: a token that attends to a
#: flipped one inherits a little of its change), none of 5,772 beyond, whose
#: widest gap is 0.052.  Ranks 8 and 9 of 128 lie 0.06 apart on average;
#: 48% of the tokens are decided in all four layers
ROUTING_MARGIN = 0.05
#: queries one block of attention takes: (heads, 256, T) float32 scores
Q_BLOCK = 256


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return dict(h=cfg["hidden_size"], H=cfg["num_attention_heads"],
                KV=cfg["num_key_value_heads"], D=cfg["head_dim"],
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"], E=cfg["router_experts"],
                held=cfg["num_experts"], first=cfg["experts_first"],
                k=cfg["num_experts_per_tok"], ns=cfg["num_shared_experts"])


def seed_key(seed: int):
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _mat(k, shape, std=INIT_STD):
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(jnp.bfloat16)


def _scale(k, n):
    return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)
            ).astype(jnp.bfloat16)


def _expert(key, h, F):
    """One expert's three matrices from its own key."""
    ks = jax.random.split(key, 3)
    return _mat(ks[0], (h, F)), _mat(ks[1], (h, F)), _mat(ks[2], (F, h))


@functools.partial(jax.jit, static_argnames=(
    "h", "H", "KV", "D", "F", "E", "held", "ns", "wo_std"))
def _layer(key, i, first, *, h, H, KV, D, F, E, held, ns, wo_std):
    key = jax.random.fold_in(key, i)
    ks = jax.random.split(key, 8)
    # expert by expert, one in flight: routed expert e from fold_in(e),
    # shared expert s from fold_in(E + s)
    routed = jax.lax.map(
        lambda e: _expert(jax.random.fold_in(ks[6], e), h, F),
        first + jnp.arange(held))
    shared = jax.lax.map(
        lambda s: _expert(jax.random.fold_in(ks[6], E + s), h, F),
        jnp.arange(ns))
    return {"wq": _mat(ks[0], (h, H * D)), "wk": _mat(ks[1], (h, KV * D)),
            "wv": _mat(ks[2], (h, KV * D)), "wo": _mat(ks[3], (H * D, h), wo_std),
            "ln": _scale(ks[4], h), "router": _mat(ks[5], (h, E)),
            "experts_gate": routed[0], "experts_up": routed[1],
            "experts_down": routed[2],
            # the shared experts side by side: (h, ns F), (h, ns F), (ns F, h)
            "shared_gate": jnp.moveaxis(shared[0], 0, 1).reshape(h, ns * F),
            "shared_up": jnp.moveaxis(shared[1], 0, 1).reshape(h, ns * F),
            "shared_down": shared[2].reshape(ns * F, h)}


@functools.partial(jax.jit, static_argnames=("h", "V"))
def _outer(key, *, h, V):
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {"embed": _mat(ks[0], (V, h)), "ln_final": _scale(ks[1], h)}


def layer_weights(cfg: Dict[str, Any], seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights on the device, bfloat16: the attention
    matrices, the norm's scale, the router over all ``router_experts``,
    the held routed experts stacked ``(held, ...)``, the shared experts
    side by side.  One compiled program for every layer (``i`` and the
    first held expert are operands), so the harness and the reference get
    the same bits."""
    d = dims(cfg)
    return _layer(seed_key(seed), jnp.asarray(i, jnp.int32),
                  jnp.asarray(d["first"], jnp.int32), h=d["h"], H=d["H"],
                  KV=d["KV"], D=d["D"], F=d["F"], E=d["E"], held=d["held"],
                  ns=d["ns"],
                  wo_std=float(cfg.get("o_proj_init_std", INIT_STD)))


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
    elif quant not in (None, "no_window"):
        raise ValueError(f"unknown control {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


# -- the equations -------------------------------------------------------------

def layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope_interleaved(x, theta):
    """x (T, heads, D) at positions 0..T-1: the pair (2i, 2i+1) turns by
    ``position * theta^(-2i/D)``."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(h, w, *, H, KV, D, theta, window, quant):
    """h (T, hidden) -> (T, hidden).  ``theta`` None: no positional
    embedding; ``window`` None: every earlier key."""
    T = h.shape[0]
    q = _mm(h, w["wq"], quant).reshape(T, H, D)
    k = _mm(h, w["wk"], quant).reshape(T, KV, D)
    v = _mm(h, w["wv"], quant).reshape(T, KV, D)
    if theta is not None:
        q, k = rope_interleaved(q, theta), rope_interleaved(k, theta)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    bq = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(args):
        qb, i = args                              # (bq, H, D), (bq,)
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) / np.sqrt(D)
        j = jnp.arange(T)[None, :]
        see = j <= i[:, None]
        if window is not None:
            see &= j > i[:, None] - window
        p = jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(T // bq, bq, H, D),
                            jnp.arange(T).reshape(T // bq, bq)))
    return _mm(o.reshape(T, H * D), w["wo"], quant)


def swiglu(h, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(h, w_gate, quant)) * _mm(h, w_up, quant),
               w_down, quant)


def route(h, w_router, *, k, quant):
    """-> (experts (T, k), weights (T, k)): the ``k`` largest sigmoid
    scores of each token over all the router's experts, normalised to sum
    to one."""
    s = jax.nn.sigmoid(_mm(h, w_router, quant))
    top, idx = jax.lax.top_k(s, k)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True)


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


def experts(h, w, *, k, first, ns, quant):
    """The held routed experts' weighted terms plus the shared experts'
    average.  Expert by expert: each held expert over every token, times
    the token's weight for it (zero where it was not selected)."""
    idx, wt = route(h, w["router"], k=k, quant=quant)

    def one(acc, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(idx == e, wt, 0.0), axis=-1)     # (T,)
        return acc + w_e[:, None] * swiglu(h, wg, wu, wd, quant), None

    held = w["experts_gate"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (first + jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    # side by side, the down-projection sums the shared experts
    shared = swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant) / ns
    return routed + shared


@functools.partial(jax.jit, static_argnames=(
    "kind", "H", "KV", "D", "theta", "window", "k", "ns", "eps", "quant"))
def block(x, w, first, *, kind, H, KV, D, theta, window, k, ns, eps,
          quant=None):
    """One decoder block over one row: x (T, hidden) float32 -> the row
    after the block, and its tokens' ``routing_margin`` in this layer."""
    sliding = kind == "sliding_attention"
    h = layer_norm(x, w["ln"], eps)
    a = attention(h, w, H=H, KV=KV, D=D, theta=theta if sliding else None,
                  window=window if sliding and quant != "no_window" else None,
                  quant=quant)
    margin = routing_margin(_mm(h, w["router"], quant), k=k, first=first,
                            held=w["experts_gate"].shape[0])
    return x + a + experts(h, w, k=k, first=first, ns=ns, quant=quant), margin


@functools.partial(jax.jit, static_argnames=("eps", "scale", "quant"))
def head(x, outer, *, eps, scale, quant=None):
    return scale * _mm(layer_norm(x, outer["ln_final"], eps),
                       outer["embed"].T, quant)


def forward_margins(cfg: Dict[str, Any], seed: int, rows: Sequence[np.ndarray],
                    want: Sequence[np.ndarray], pad_to: int,
                    quant: Optional[str] = None):
    """Logits of each row of token ids at its ``want`` positions, and the
    least ``routing_margin`` over the layers at the same positions.

    Layer by layer, the layer's weights made anew from the seed, every row
    through it in turn, so that one layer's weights and one block of one
    row's scores are all the device holds.  Rows are padded to ``pad_to``
    tokens (one compiled shape); the masks are causal, so the padding
    changes nothing before it.  Returns float32 arrays (len(want[i]),
    vocab) and (len(want[i]),)."""
    d = dims(cfg)
    outer = outer_weights(cfg, seed)
    eps = float(cfg["layer_norm_eps"])
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
        w = layer_weights(cfg, seed, i)
        for n, x in enumerate(xs):
            xs[n], m = block(x, w, first, kind=cfg["layer_types"][i],
                             H=d["H"], KV=d["KV"], D=d["D"],
                             theta=float(cfg["rope_theta"]),
                             window=int(cfg["sliding_window"]), k=d["k"],
                             ns=d["ns"], eps=eps, quant=quant)
            margins[n] = jnp.minimum(margins[n], m)
        del w
    want = [np.asarray(pos, np.int32) for pos in want]
    return ([np.asarray(head(x[jnp.asarray(pos)], outer, eps=eps,
                             scale=float(cfg["logit_scale"]), quant=quant))
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
    and the gap read is that of the token IT puts first."""
    rows = [np.asarray(list(p) + list(o[:-1]), np.int32)
            for p, o in zip(prompts, served)]
    want = [np.arange(len(p) - 1, len(p) - 1 + len(o))
            for p, o in zip(prompts, served)]
    ref, margins = forward_margins(cfg, seed, rows, want, pad_to)
    low = forward(cfg, seed, rows, want, pad_to, control) if control else None
    gaps = []
    for r, (lg, o) in enumerate(zip(ref, served)):
        tok = (np.asarray(o, np.int64) if low is None
               else low[r].argmax(-1))
        gaps.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
    allg = np.concatenate(gaps)
    decided = np.concatenate(margins) > ROUTING_MARGIN
    return {"widest_gap": float(allg[decided].max()) if decided.any()
            else float("nan"),
            "tokens": int(decided.sum()), "tokens_undecided":
            int((~decided).sum()), "widest_gap_all": float(allg.max()),
            "mismatches": int((allg > 0).sum()),
            "logit_std": float(np.mean([lg.std() for lg in ref]))}
