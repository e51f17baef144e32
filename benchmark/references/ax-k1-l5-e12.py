"""Plain reference of the A.X-K1 language model (``config.json`` of
``skt/A.X-K1``: ``model_type`` ``axk1``; DeepSeek-V3's latent attention and
grouped sigmoid router at its own numbers), as ONE chip of a deployment holds
it: the routed experts ``experts_first .. experts_first + n_routed_experts`` of
the router's ``router_experts``.

One layer, ``x`` a token's residual (pre-norm, no bias anywhere,
``rms(z) = z / sqrt(mean(z^2) + eps) * g``):

    h = rms(x)
    c_q = rms_q(h W_DQ)  (q_lora_rank);  q = c_q W_UQ, per head
        [q_nope (qk_nope_head_dim) | q_pe (qk_rope_head_dim)]
    [c (kv_lora_rank) | k_pe (qk_rope_head_dim)] = h W_DKV;  c <- rms_kv(c)
    q_pe, k_pe turned by YaRN's rotary embedding (pairs (2i, 2i + 1); see
        ``yarn``); ONE k_pe for every head
    [k_nope_h | v_h] = c W_UKV,h;  k_h = [k_nope_h | k_pe]
    s_h,ij = q_h,i . k_h,j * sigma, j <= i;  sigma = (dn + dr)^-0.5 m^2,
        m = 0.1 mscale_all_dim ln(factor) + 1
    x <- x + concat_h(softmax_j(s_h,ij) v_h,j) W_O
    h2 = rms(x)
    dense layer (i < first_k_dense_replace):
        x <- x + W_down (silu(W_gate h2) * W_up h2)
    expert layer: s = sigmoid(h2 W_r) over router_experts; the experts lie in
        n_group groups of router_experts / n_group by index, a group scores
        the sum of its two largest s, the topk_group best groups are kept;
        T = the num_experts_per_tok experts with the largest s among the kept;
        w_e = routed_scaling_factor * s_e / sum_T s;
        x <- x + sum_{e in T, e held here} w_e E_e(h2) + E_shared(h2),
        E(h) = W_down (silu(W_gate h) * W_up h), the shared expert SUMMED
        (weight 1)

After the last layer ``rms``, logits ``= h W_head``, the head untied.  The
terms of absent experts are left out, as the program leaves them out (nothing
stands in for the chips that would compute them).  With ``experts_first`` 0
and ``n_routed_experts == router_experts`` this is the uncut layer.  What the
config does not state is listed under ``assumed`` in the configuration file.

float32 ``jax.numpy`` with ``precision=HIGHEST``: the EXPANDED form only (the
program also runs the absorbed one), no kernel, no cache, no bucket; a held
expert is applied to every token and weighted by ``w_e`` (zero where the token
did not select it); attention in blocks of queries so that the scores of 64
heads over 17,920 keys fit.  It imports nothing of the program; the weights are
made HERE from the seed, bfloat16, layer by layer and expert by expert (expert
``e``'s weights depend on ``e`` alone, so every share of a layer sees the same
expert), and the harness hands the same arrays to the program.

Controls (``forward(quant=...)``): ``"fp8"`` rounds every matrix product's two
operands to float8_e4m3; ``"no_yarn"`` turns by the plain frequencies
``theta^(-2i/dr)`` and scores by ``(dn + dr)^-0.5``; ``"no_kv_norm"`` leaves
``c`` un-normed.

What ``served_gaps`` compares: the widest gap over the DECIDED tokens, as the
MiMo-V2.5 reference reads it, with the groups beside the experts: a token is
decided where, in every expert layer, group 0's score (the group of every
held expert) lies clear of the edge of the kept groups, and, where group 0 is
kept, every held expert lies clear of the edge of the token's top 8 among the
kept experts, both by more than ``ROUTING_MARGIN`` of the spread of the
token's scores.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
#: W_UQ and W_UKV are drawn wider than the rest: at 0.02 a head's scores over
#: a 16,384-token preamble spread by some 0.6, attention averages thousands of
#: values to almost nothing and the latent path (the mechanism under test)
#: moves a logit by less than bfloat16 does.  At these spreads a query's
#: scores spread by some 4, a few keys take most of its mass, and attention's
#: term in the residual is of the feed-forward's order, so a fault in the
#: latent path is seen in the logits
Q_UP_STD, KV_UP_STD = 0.04, 0.06
#: how far group 0 and every held expert have to lie from their edges for a
#: token to be compared (module docstring), in standard deviations of the
#: token's scores over the router's experts (PERF.md section 2)
ROUTING_MARGIN = 0.02
#: queries one block of attention takes: (64 heads, 128, 17,920) float32
#: scores are 0.59 GB
Q_BLOCK = 128
CONTROLS = ("fp8", "no_yarn", "no_kv_norm")


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    return dict(h=cfg["hidden_size"], H=cfg["num_attention_heads"],
                qr=cfg["q_lora_rank"], r=cfg["kv_lora_rank"],
                dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], F=cfg["intermediate_size"],
                Fe=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"], E=cfg["router_experts"],
                held=cfg["n_routed_experts"], first=cfg["experts_first"],
                k=cfg["num_experts_per_tok"], G=cfg["n_group"],
                Gk=cfg["topk_group"],
                scale=float(cfg["routed_scaling_factor"]),
                dense=int(cfg["first_k_dense_replace"]))


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
    "h", "H", "qr", "r", "dn", "dr", "dv", "F", "Fe", "E", "held", "moe"))
def _layer(key, i, first, *, h, H, qr, r, dn, dr, dv, F, Fe, E, held, moe):
    key = jax.random.fold_in(key, i)
    ks = jax.random.split(key, 14)
    w = {"wq_a": _mat(ks[0], (h, qr)), "q_a_norm": _scale(ks[1], qr),
         "wq_b": _mat(ks[2], (qr, H * (dn + dr)), Q_UP_STD),
         "wkv_a": _mat(ks[3], (h, r + dr)), "kv_a_norm": _scale(ks[4], r),
         "wkv_b": _mat(ks[5], (r, H * (dn + dv)), KV_UP_STD).reshape(
             r, H, dn + dv),
         "wo": _mat(ks[6], (H * dv, h)),
         "ln_attn": _scale(ks[7], h), "ln_mlp": _scale(ks[8], h)}
    if not moe:
        w["w_gate"], w["w_up"], w["w_down"] = _expert(ks[9], h, F)
        return w
    # expert by expert, one in flight: routed expert e from fold_in(e)
    routed = jax.lax.map(
        lambda e: _expert(jax.random.fold_in(ks[10], e), h, Fe),
        first + jnp.arange(held))
    w["shared_gate"], w["shared_up"], w["shared_down"] = _expert(ks[11], h, Fe)
    w.update(router=_mat(ks[12], (h, E)), experts_gate=routed[0],
             experts_up=routed[1], experts_down=routed[2])
    return w


@functools.partial(jax.jit, static_argnames=("h", "V"))
def _outer(key, *, h, V):
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {"embed": _mat(ks[0], (V, h)), "ln_final": _scale(ks[1], h),
            "head": _mat(ks[2], (h, V))}


def layer_weights(cfg: Dict[str, Any], seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights on the device: latent attention's seven (W_UKV
    by head, ``(r, H, dn + dv)``), the two norms' scales, and the dense
    feed-forward's three matrices (layers before ``first_k_dense_replace``)
    or the router over all ``router_experts``, the shared expert and the held
    routed experts stacked ``(held, ...)``.  One compiled program a kind of
    layer (``i`` and the first held expert are operands), so the harness and
    the reference get the same bits."""
    d = dims(cfg)
    return _layer(seed_key(seed), jnp.asarray(i, jnp.int32),
                  jnp.asarray(d["first"], jnp.int32), h=d["h"], H=d["H"],
                  qr=d["qr"], r=d["r"], dn=d["dn"], dr=d["dr"], dv=d["dv"],
                  F=d["F"], Fe=d["Fe"], E=d["E"], held=d["held"],
                  moe=i >= d["dense"])


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


def yarn(cfg: Dict[str, Any], plain: bool = False):
    """(inverse frequencies (dr/2,) float32, scale of cos and sin, sigma).

    YaRN (DeepSeek-V3's ``yarn_find_correction_range`` and
    ``yarn_get_mscale``): base f_i = theta^(-2i/dr); with
    dim(b) = dr ln(orig / (2 pi b)) / (2 ln theta), low = floor(dim(beta_fast))
    and high = ceil(dim(beta_slow)) clamped to [0, dr - 1], the ramp
    r_i = clip((i - low) / (high - low), 0, 1) and f'_i = f_i / factor r_i +
    f_i (1 - r_i).  ``plain``: f_i itself and sigma = (dn + dr)^-0.5."""
    rs, dr = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_theta"])
    base = theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    sigma = (cfg["qk_nope_head_dim"] + dr) ** -0.5
    if plain:
        return base.astype(np.float32), 1.0, sigma
    factor, orig = float(rs["factor"]), float(
        rs["original_max_position_embeddings"])

    def dim(b):
        return dr * np.log(orig / (2 * np.pi * b)) / (2 * np.log(theta))
    low = max(np.floor(dim(float(rs["beta_fast"]))), 0)
    high = min(np.ceil(dim(float(rs["beta_slow"]))), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / (high - low), 0, 1)
    freq = base / factor * ramp + base * (1 - ramp)

    def m(a):
        return 1.0 if factor <= 1 or not a else 0.1 * a * np.log(factor) + 1
    cs = m(float(rs["mscale"])) / m(float(rs["mscale_all_dim"]))
    return (freq.astype(np.float32), cs,
            sigma * m(float(rs["mscale_all_dim"])) ** 2)


def rope_pairs(x, freq, cs):
    """x (T, heads, dr) at positions 0..T-1, pairs (2i, 2i + 1) turned by
    ``position * freq[i]``, cos and sin scaled by ``cs``."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(freq)[None]
    cos, sin = cs * jnp.cos(ang)[:, None, :], cs * jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def attention(h, w, *, H, r, dn, dr, dv, eps, freq, cs, sigma, quant):
    """h (T, hidden) -> (T, hidden): latent attention, expanded."""
    T = h.shape[0]
    cq = rms_norm(_mm(h, w["wq_a"], quant), w["q_a_norm"], eps)
    q = _mm(cq, w["wq_b"], quant).reshape(T, H, dn + dr)
    kv = _mm(h, w["wkv_a"], quant)
    c = kv[:, :r] if quant == "no_kv_norm" else \
        rms_norm(kv[:, :r], w["kv_a_norm"], eps)
    k_pe = rope_pairs(kv[:, None, r:], freq, cs)                  # (T, 1, dr)
    q = jnp.concatenate([q[..., :dn], rope_pairs(q[..., dn:], freq, cs)], -1)
    up = _mm(c, w["wkv_b"].reshape(r, H * (dn + dv)), quant).reshape(
        T, H, dn + dv)
    k = jnp.concatenate([up[..., :dn], jnp.broadcast_to(k_pe, (T, H, dr))],
                        -1)
    v = up[..., dn:]
    bq = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(args):
        qb, i = args                              # (bq, H, dn + dr), (bq,)
        s = jnp.einsum("thd,shd->hts", qb, k, precision=HIGHEST) * sigma
        s = jnp.where((jnp.arange(T)[None, :] <= i[:, None])[None], s,
                      -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(T // bq, bq, H, dn + dr),
                            jnp.arange(T).reshape(T // bq, bq)))
    return _mm(o.reshape(T, H * dv), w["wo"], quant)


def swiglu(h, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(h, w_gate, quant)) * _mm(h, w_up, quant),
               w_down, quant)


def route(h, w_router, *, k, G, Gk, scale, quant):
    """-> (experts (T, k), weights (T, k), scores (T, E), kept groups (T, G)
    bool, group scores (T, G))."""
    s = jax.nn.sigmoid(_mm(h, w_router, quant))
    T, E = s.shape
    gs = jnp.sum(jax.lax.top_k(s.reshape(T, G, E // G), 2)[0], -1)   # (T, G)
    kept = jnp.any(jax.lax.top_k(gs, Gk)[1][:, :, None] == jnp.arange(G),
                   axis=1)
    sel = jnp.where(jnp.repeat(kept, E // G, axis=1), s, -jnp.inf)
    _, idx = jax.lax.top_k(sel, k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * top / jnp.sum(top, -1, keepdims=True), s, kept, gs


def routing_margin(s, kept, gs, *, k, Gk, first, held):
    """The least distance of a held expert's group from the edge of the kept
    groups and, where that group is kept, of a held expert from the edge of
    the token's top ``k`` among the kept experts, in standard deviations of
    the token's scores.  Every held expert lies in the group of the first
    (the configuration's held experts are whole groups or part of one)."""
    T, E = s.shape
    G = gs.shape[1]
    g = first // (E // G)
    in_g = jax.lax.dynamic_index_in_dim(kept, g, axis=1, keepdims=False)
    gs_g = jax.lax.dynamic_index_in_dim(gs, g, axis=1, keepdims=False)
    ranked = jnp.sort(gs, axis=-1)
    gk, gn = ranked[:, -Gk], ranked[:, -Gk - 1]
    gm = jnp.where(in_g, gs_g - gn, gk - gs_g)
    sel = jnp.where(jnp.repeat(kept, E // G, axis=1), s, -jnp.inf)
    top = jnp.sort(sel, axis=-1)
    kth, nxt = top[:, -k, None], top[:, -k - 1, None]
    sh = jax.lax.dynamic_slice_in_dim(s, first, held, axis=1)
    em = jnp.min(jnp.where(sh >= kth, sh - nxt, kth - sh), axis=-1)
    m = jnp.where(in_g, jnp.minimum(gm, em), gm)
    return m / jnp.std(s, axis=-1)


def experts(h, w, *, k, G, Gk, scale, first, quant):
    """The held routed experts' weighted terms plus the shared expert's.
    -> (terms (T, hidden), routing margin (T,))."""
    idx, wt, s, kept, gs = route(h, w["router"], k=k, G=G, Gk=Gk,
                                 scale=scale, quant=quant)

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
                    quant)
    return routed + shared, routing_margin(s, kept, gs, k=k, Gk=Gk,
                                           first=first, held=held)


@functools.partial(jax.jit, static_argnames=(
    "H", "r", "dn", "dr", "dv", "k", "G", "Gk", "scale", "eps", "cs",
    "sigma", "quant"))
def block(x, w, first, freq, *, H, r, dn, dr, dv, k, G, Gk, scale, eps, cs,
          sigma, quant=None):
    """One decoder block over one row: x (T, hidden) float32 -> the row
    after the block, and its tokens' routing margin in this layer (infinite
    in a dense layer: nothing is selected there)."""
    x = x + attention(rms_norm(x, w["ln_attn"], eps), w, H=H, r=r, dn=dn,
                      dr=dr, dv=dv, eps=eps, freq=freq, cs=cs, sigma=sigma,
                      quant=quant)
    h2 = rms_norm(x, w["ln_mlp"], eps)
    if "router" not in w:
        return x + swiglu(h2, w["w_gate"], w["w_up"], w["w_down"], quant), \
            jnp.full(x.shape[0], jnp.inf, jnp.float32)
    terms, margin = experts(h2, w, k=k, G=G, Gk=Gk, scale=scale, first=first,
                            quant=quant)
    return x + terms, margin


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, outer, *, eps, quant=None):
    return _mm(rms_norm(x, outer["ln_final"], eps), outer["head"], quant)


def forward_margins(cfg: Dict[str, Any], seed: int, rows: Sequence[np.ndarray],
                    want: Sequence[np.ndarray], pad_to: int,
                    quant: Optional[str] = None):
    """Logits of each row of token ids at its ``want`` positions, and the
    least routing margin over the expert layers at the same positions.

    Layer by layer, the layer's weights made anew from the seed, every row
    through it in turn, so that one layer's weights and one block of one
    row's scores are all the device holds.  Rows are padded to ``pad_to``
    tokens (one compiled shape); the masks are causal, so the padding
    changes nothing before it."""
    d = dims(cfg)
    outer = outer_weights(cfg, seed)
    eps = float(cfg["rms_norm_eps"])
    first = jnp.asarray(d["first"], jnp.int32)
    freq, cs, sigma = yarn(cfg, plain=quant == "no_yarn")
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
            xs[n], m = block(
                x, w, first, jnp.asarray(freq), H=d["H"], r=d["r"], dn=d["dn"],
                dr=d["dr"], dv=d["dv"], k=d["k"], G=d["G"], Gk=d["Gk"],
                scale=d["scale"], eps=eps, cs=float(cs), sigma=float(sigma),
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
