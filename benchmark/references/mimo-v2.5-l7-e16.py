"""Plain reference of the MiMo-V2.5 language model (``config.json`` of
``XiaomiMiMo/MiMo-V2.5``: ``model_type`` ``mimo_v2``), as ONE chip of a
deployment holds it: the routed experts ``experts_first .. experts_first +
n_routed_experts`` of the router's ``router_experts``.

One layer, ``x`` a token's residual (``hybrid_layer_pattern[i]`` 0: full
attention, 1: sliding window; ``moe_layer_freq[i]`` 0: dense feed-forward, 1:
experts; pre-norm, no bias anywhere):

    h = rms(x) = x / sqrt(mean(x^2) + eps) * g
    q = h Wq (H heads of D), k = h Wk (KV heads of D), v = h Wv (KV heads
        of Dv);  KV = num_key_value_heads on full layers,
        swa_num_key_value_heads on window layers
    rotary on the FIRST int(D * partial_rotary_factor) dims of every q and k
        head, pairs (i, i + rot/2) half-split, the rest untouched; theta
        rope_theta on full layers, swa_rope_theta on window layers
    v <- attention_value_scale * v
    s_ij = q_i . k_j / sqrt(D);  full: j <= i;  window: i - W < j <= i
    window layers: a sink logit b_h a query head,
        p_ij = exp(s_ij - m) / (exp(b_h - m) + sum_j' exp(s_ij' - m))
        (one more column in the softmax: it takes mass, it has no value)
    x <- x + concat_h(sum_j p_ij v_j) Wo
    h2 = rms(x)
    dense layer:   x <- x + Wdown (silu(Wgate h2) * Wup h2)
    expert layer:  r = h2 Wr (router_experts logits), s = sigmoid(r);
        T = the num_experts_per_tok experts with the largest s_e + c_e
        (c a bias an expert: it acts on the SELECTION only);
        w_e = s_e / sum_{T} s   (without c);
        x <- x + sum_{e in T, e held here} w_e E_e(h2),
        E(h) = Wdown (silu(Wgate h) * Wup h)

After the last layer ``rms``, logits ``= h Whead``, the head untied.  ``w_e``
is normalised over all the selected experts, held here or not; the terms of
absent experts are left out, as the program leaves them out (nothing stands
in for the chips that would compute them).  With ``experts_first`` 0 and
``n_routed_experts == router_experts`` this is the uncut layer.  What the
config does not state is listed under ``assumed`` in the configuration file.

float32 ``jax.numpy`` with ``precision=HIGHEST``: no kernel, no cache, no
ring, no bucket, no grouping; a held expert is applied to every token and
weighted by ``w_e`` (zero where the token did not select it); attention in
blocks of queries so that the scores of 64 heads over 16,384 keys fit.  It
imports nothing of the program; the weights are made HERE from the seed,
bfloat16 (sinks and ``c`` float32), layer by layer and expert by expert
(expert ``e``'s weights depend on ``e`` alone, so every share of a layer sees
the same expert), and the harness hands the same arrays to the program.

Controls (``forward(quant=...)``): ``"fp8"`` rounds every matrix product's
two operands to float8_e4m3; ``"no_window"`` lets the window layers attend to
every earlier key; ``"no_sink"`` leaves the sink column out.

What ``served_gaps`` compares.  A choice of 8 among 256 turns on small gaps:
where a held expert's selection value ``s_e + c_e`` lies within a rounding of
the edge of the token's top 8, a bfloat16 run and this one may select
differently, and the token's logits then differ by a whole expert's term, in
ANY bfloat16 run, sound or not.  Such a token says nothing about the
precision of the program, so the widest gap is read over the DECIDED tokens
only: those where, in every expert layer, every held expert is inside or
outside the token's top 8 by more than ``ROUTING_MARGIN`` of the spread of
the token's selection values, as this reference computes it.  The set is
fixed by the reference alone, so the program and a control are read over the
same tokens; on a decided token a pair that is dropped or sent to the wrong
expert moves the logits by the expert's full term.  Whether the held experts'
terms are exact under any routing is held by ``tests/test_llm_moe.py``, not
by ``correct``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
#: the sinks: normal around ``SINK_MEAN``.  A window's 128 scores have a
#: spread of 1.6 or so at these weights, their logsumexp is about 6: a sink
#: there takes near half of a query's mass, as trained sinks do, so leaving
#: it out (or adding it to a full layer) is seen in every logit
SINK_MEAN, SINK_STD = 6.0, 1.0
#: the selection bias: normal, in units of a sigmoid score.  The scores of a
#: token's 8th and 9th experts of 256 lie 0.004 apart or so: at 0.05 the bias
#: changes the selected set of nearly every token, so a router that leaves it
#: out, or weighs by it, is seen
BIAS_STD = 0.05

#: how far every held expert's selection value has to lie from the edge of a
#: token's top k for the token to be compared (module docstring), in standard
#: deviations of the token's selection values over the router's experts.
#: The program's router runs in float32 on a bfloat16 residual.  On the chip
#: (five runs, 16,300 tokens; PERF.md section 2) the tokens whose served logit
#: fell 0.06 or more under the reference's best all lay within 0.02 of an edge
#: (up to 0.24 within 0.005, up to 0.13 between 0.01 and 0.02); between 0.02
#: and 0.05 the widest gap is 0.056 (0.072 over eleven runs), beyond that
#: 0.051: what bfloat16 reads without a flip.  58-61% of the tokens are decided in all six expert layers
ROUTING_MARGIN = 0.02
#: queries one block of attention takes: (64 heads, 128, 16,384) float32
#: scores are 0.5 GiB
Q_BLOCK = 128
CONTROLS = ("fp8", "no_window", "no_sink")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return dict(h=cfg["hidden_size"], H=cfg["num_attention_heads"],
                D=cfg["head_dim"], Dv=cfg["v_head_dim"],
                F=cfg["intermediate_size"], Fe=cfg["moe_intermediate_size"],
                V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
                E=cfg["router_experts"], held=cfg["n_routed_experts"],
                first=cfg["experts_first"], k=cfg["num_experts_per_tok"])


def layer_kind(cfg: Dict[str, Any], i: int) -> Dict[str, Any]:
    """What layer ``i`` is: window or full (and that kind's K/V heads,
    theta and sink), dense or experts."""
    sliding = bool(cfg["hybrid_layer_pattern"][i])
    pre = "swa_" if sliding else ""
    assert cfg.get(pre + "head_dim", cfg["head_dim"]) == cfg["head_dim"]
    assert cfg.get(pre + "v_head_dim", cfg["v_head_dim"]) == cfg["v_head_dim"]
    return dict(
        sliding=sliding, moe=bool(cfg["moe_layer_freq"][i]),
        KV=cfg["swa_num_key_value_heads" if sliding
               else "num_key_value_heads"],
        theta=float(cfg["swa_rope_theta" if sliding else "rope_theta"]),
        sink=bool(cfg["add_swa_attention_sink_bias" if sliding
                      else "add_full_attention_sink_bias"]))


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
    "h", "H", "KV", "D", "Dv", "F", "Fe", "E", "held", "sink", "moe"))
def _layer(key, i, first, *, h, H, KV, D, Dv, F, Fe, E, held, sink, moe):
    key = jax.random.fold_in(key, i)
    ks = jax.random.split(key, 12)
    w = {"wq": _mat(ks[0], (h, H * D)), "wk": _mat(ks[1], (h, KV * D)),
         "wv": _mat(ks[2], (h, KV * Dv)), "wo": _mat(ks[3], (H * Dv, h)),
         "ln_attn": _scale(ks[4], h), "ln_mlp": _scale(ks[5], h)}
    if sink:
        w["sink"] = SINK_MEAN + SINK_STD * jax.random.normal(
            ks[6], (H,), jnp.float32)
    if not moe:
        w["w_gate"], w["w_up"], w["w_down"] = _expert(ks[7], h, F)
        return w
    # expert by expert, one in flight: routed expert e from fold_in(e)
    routed = jax.lax.map(
        lambda e: _expert(jax.random.fold_in(ks[8], e), h, Fe),
        first + jnp.arange(held))
    w.update(router=_mat(ks[9], (h, E)),
             router_bias=BIAS_STD * jax.random.normal(ks[10], (E,),
                                                      jnp.float32),
             experts_gate=routed[0], experts_up=routed[1],
             experts_down=routed[2])
    return w


@functools.partial(jax.jit, static_argnames=("h", "V"))
def _outer(key, *, h, V):
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _mat(ks[0], (V, h)), "ln_final": _scale(ks[1], h),
            "head": _mat(ks[2], (h, V))}


def layer_weights(cfg: Dict[str, Any], seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights on the device: the attention matrices at the
    layer kind's K/V heads, the two norms' scales, the sinks of a window
    layer (float32), and the dense feed-forward's three matrices or the
    router over all ``router_experts``, its selection bias (float32) and the
    held routed experts stacked ``(held, ...)``.  One compiled program a
    kind of layer (``i`` and the first held expert are operands), so the
    harness and the reference get the same bits."""
    d, kind = dims(cfg), layer_kind(cfg, i)
    return _layer(seed_key(seed), jnp.asarray(i, jnp.int32),
                  jnp.asarray(d["first"], jnp.int32), h=d["h"], H=d["H"],
                  KV=kind["KV"], D=d["D"], Dv=d["Dv"], F=d["F"], Fe=d["Fe"],
                  E=d["E"], held=d["held"], sink=kind["sink"],
                  moe=kind["moe"])


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

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def rope_part(x, theta, rot):
    """x (T, heads, D) at positions 0..T-1: the first ``rot`` dims turn, pair
    (i, i + rot/2) by ``position * theta^(-2i/rot)``; the rest pass."""
    T = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def attention(h, w, *, H, KV, D, Dv, theta, rot, window, value_scale, quant):
    """h (T, hidden) -> (T, hidden).  ``window`` None: every earlier key;
    ``w["sink"]``, where the layer has one: a logit a head beside the keys'."""
    T = h.shape[0]
    q = rope_part(_mm(h, w["wq"], quant).reshape(T, H, D), theta, rot)
    k = rope_part(_mm(h, w["wk"], quant).reshape(T, KV, D), theta, rot)
    v = value_scale * _mm(h, w["wv"], quant).reshape(T, KV, Dv)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    sink = w.get("sink") if quant != "no_sink" else None
    bq = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(args):
        qb, i = args                              # (bq, H, D), (bq,)
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) / np.sqrt(D)
        j = jnp.arange(T)[None, :]
        see = j <= i[:, None]
        if window is not None:
            see &= j > i[:, None] - window
        s = jnp.where(see[None], s, -jnp.inf)
        if sink is not None:                      # one more column, no value
            col = jnp.broadcast_to(sink[:, None, None], (H, qb.shape[0], 1))
            s = jnp.concatenate([s, col], axis=-1)
        p = jax.nn.softmax(s, axis=-1)[..., :T]
        return jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(T // bq, bq, H, D),
                            jnp.arange(T).reshape(T // bq, bq)))
    return _mm(o.reshape(T, H * Dv), w["wo"], quant)


def swiglu(h, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(h, w_gate, quant)) * _mm(h, w_up, quant),
               w_down, quant)


def route(h, w_router, bias, *, k, quant):
    """-> (experts (T, k), weights (T, k), selection values (T, E)): the
    ``k`` largest ``sigmoid(r) + bias`` of each token over all the router's
    experts; the weights are the selected SCORES, without the bias,
    normalised to sum to one."""
    s = jax.nn.sigmoid(_mm(h, w_router, quant))
    sel = s + bias
    _, idx = jax.lax.top_k(sel, k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, top / jnp.sum(top, axis=-1, keepdims=True), sel


def routing_margin(r, *, k, first, held):
    """r (T, E) selection values -> (T,): the least distance of a held
    expert's value from the edge it would cross to enter or leave the
    token's top ``k`` (the k+1-th largest value for a selected expert, the
    k-th for any other), in standard deviations of the token's values."""
    ranked = jnp.sort(r, axis=-1)
    kth, nxt = ranked[:, -k, None], ranked[:, -k - 1, None]
    rh = jax.lax.dynamic_slice_in_dim(r, first, held, axis=1)
    return jnp.min(jnp.where(rh >= kth, rh - nxt, kth - rh), axis=-1) \
        / jnp.std(r, axis=-1)


def experts(h, w, *, k, first, quant):
    """The held routed experts' weighted terms.  Expert by expert: each held
    expert over every token, times the token's weight for it (zero where it
    was not selected).  -> (terms (T, hidden), selection values (T, E))."""
    idx, wt, sel = route(h, w["router"], w["router_bias"], k=k, quant=quant)

    def one(acc, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(idx == e, wt, 0.0), axis=-1)     # (T,)
        return acc + w_e[:, None] * swiglu(h, wg, wu, wd, quant), None

    held = w["experts_gate"].shape[0]
    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (first + jnp.arange(held), w["experts_gate"], w["experts_up"],
         w["experts_down"]))
    return routed, sel


@functools.partial(jax.jit, static_argnames=(
    "H", "KV", "D", "Dv", "theta", "rot", "window", "value_scale", "k",
    "eps", "quant"))
def block(x, w, first, *, H, KV, D, Dv, theta, rot, window, value_scale, k,
          eps, quant=None):
    """One decoder block over one row: x (T, hidden) float32 -> the row
    after the block, and its tokens' ``routing_margin`` in this layer
    (infinite in a dense layer: nothing is selected there)."""
    a = attention(rms_norm(x, w["ln_attn"], eps), w, H=H, KV=KV, D=D, Dv=Dv,
                  theta=theta, rot=rot,
                  window=None if quant == "no_window" else window,
                  value_scale=value_scale, quant=quant)
    x = x + a
    h2 = rms_norm(x, w["ln_mlp"], eps)
    if "router" not in w:
        return x + swiglu(h2, w["w_gate"], w["w_up"], w["w_down"], quant), \
            jnp.full(x.shape[0], jnp.inf, jnp.float32)
    routed, sel = experts(h2, w, k=k, first=first, quant=quant)
    margin = routing_margin(sel, k=k, first=first,
                            held=w["experts_gate"].shape[0])
    return x + routed, margin


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, outer, *, eps, quant=None):
    return _mm(rms_norm(x, outer["ln_final"], eps), outer["head"], quant)


def forward_margins(cfg: Dict[str, Any], seed: int, rows: Sequence[np.ndarray],
                    want: Sequence[np.ndarray], pad_to: int,
                    quant: Optional[str] = None):
    """Logits of each row of token ids at its ``want`` positions, and the
    least ``routing_margin`` over the expert layers at the same positions.

    Layer by layer, the layer's weights made anew from the seed, every row
    through it in turn, so that one layer's weights and one block of one
    row's scores are all the device holds.  Rows are padded to ``pad_to``
    tokens (one compiled shape); the masks are causal, so the padding
    changes nothing before it.  Returns float32 arrays (len(want[i]),
    vocab) and (len(want[i]),)."""
    d = dims(cfg)
    outer = outer_weights(cfg, seed)
    eps = float(cfg["layernorm_epsilon"])
    first = jnp.asarray(d["first"], jnp.int32)
    rot = int(d["D"] * float(cfg["partial_rotary_factor"]))
    xs = []
    for ids in rows:
        if len(ids) > pad_to:
            raise ValueError(f"row of {len(ids)} tokens > pad_to={pad_to}")
        padded = np.zeros(pad_to, np.int32)
        padded[:len(ids)] = ids
        xs.append(outer["embed"][jnp.asarray(padded)].astype(jnp.float32))
    margins = [jnp.full(pad_to, jnp.inf, jnp.float32) for _ in rows]
    for i in range(d["L"]):
        w, kind = layer_weights(cfg, seed, i), layer_kind(cfg, i)
        for n, x in enumerate(xs):
            xs[n], m = block(
                x, w, first, H=d["H"], KV=kind["KV"], D=d["D"], Dv=d["Dv"],
                theta=kind["theta"], rot=rot,
                window=int(cfg["sliding_window"]) if kind["sliding"] else None,
                value_scale=float(cfg["attention_value_scale"]), k=d["k"],
                eps=eps, quant=quant)
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
    reading ``ROUTING_MARGIN`` was chosen from."""
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
