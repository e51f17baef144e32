"""Plain reference of histogram gradient boosting as the configuration states
it (LightGBM's algorithm, Ke et al. 2017, binary log-loss): quantile bins
from a row sample, per-node histograms of gradient, hessian and count, the
split that maximises ``GL^2/HL + GR^2/HR - G^2/H``, leaves of
``-lr * G / (H + lambda_l2)``.

It does not grow trees of its own: whether two growers agree is decided by
near-ties.  It CHECKS the trees the timed fit produced, one by one, as a
served token is checked against the reference's logits: with its own bins,
its own margins (following the program's trees, as the reference of a served
model follows the served tokens), its own gradients and its own exact
float64 histograms, it reads for each checked tree

- ``split_gain_gap``: by how much the gain of the split the program chose at
  a node lies below the best gain over the candidates the configuration
  allows there, as a share of that best gain or of the tree's median node's
  best gain, whichever is larger (the widest over the nodes; a node whose
  best gain is a thousandth of the root's orders its near-equal candidates
  by rounding alone, so its own gain is no yardstick; a cut that gains more
  than the best allowed candidate reads below zero and counts as zero);
- ``leaf_value_gap``: the program's leaf value against ``-lr * G / H`` from
  the reference's sums, relative to the larger of that leaf's and the
  median leaf's value (the widest over the leaves);
- ``node_count_gap``: the rows the program says a node covers against the
  rows the reference routes there (the widest over the nodes, in rows);

and ``predict_gap``: the program's own predictor over every tree of the fit
against this file's walk of the same trees, on a seeded sample of rows,
relative to the walk's root-mean-square margin.

Candidates under two-level histograms (the configuration's ``two_level``):
every cut of the ``refine_features`` features whose best coarse cut gains
most at the root, and for the other features the cuts at coarse-bin
boundaries (fine bin ``8c + 7``).

The data are made here from the seed (:func:`make_data`).  ``low=`` of
:func:`check_fit` rounds each gradient and hessian before it enters a
histogram (the sums are this file's own, float64 across chunks): ``None``
keeps float32;
``"bfloat16"`` is the ingest the HIGGS configuration states, and the one its
runs are checked in (against float32 gradients the rounding alone moves a
leaf by 0.003-0.005 and reorders near-equal cuts at weak nodes); ``"fp8"``
(float8_e4m3) is the control, one precision below, whose ``leaf_ref`` the
runner puts in the program's place.  Imports nothing of the program.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 1 << 16


def make_data(cfg: Dict[str, Any], seed: int):
    """(X (rows, features) float32, y (rows,) float64 in {0, 1}): standard
    normal features, the label a noisy rule of four of them (the shape of
    the HIGGS table: dense floats, binary label)."""
    rng = np.random.default_rng([int(seed), 11])
    X = rng.standard_normal((cfg["rows"], cfg["features"]), dtype=np.float32)
    noise = rng.standard_normal(cfg["rows"], dtype=np.float32)
    y = (X[:, 0] * 2 - X[:, 1] + X[:, 2] * X[:, 3] + 0.5 * noise > 0)
    return X, y.astype(np.float64)


def bin_bounds(cfg: Dict[str, Any], X: np.ndarray) -> np.ndarray:
    """(features, max_bin) upper bounds, +inf past a feature's last: the
    ``max_bin - 1`` inner quantiles of a sample of ``bin_sample_count``
    rows drawn without replacement by ``default_rng(bin_seed)``."""
    n, f = X.shape
    mb = cfg["max_bin"]
    if n > cfg["bin_sample_count"]:
        pick = np.random.default_rng(cfg["bin_seed"]).choice(
            n, cfg["bin_sample_count"], replace=False)
        sample = X[pick]
    else:
        sample = X
    upper = np.full((f, mb), np.inf, np.float32)
    for j in range(f):
        col = sample[:, j]
        uniq = np.unique(col)
        if len(uniq) <= mb:
            b = (uniq[:-1] + uniq[1:]) / 2
        else:
            b = np.unique(np.quantile(col, np.linspace(0, 1, mb + 1)[1:-1]
                                      ).astype(np.float32))
        upper[j, :len(b)] = b
    return upper


@jax.jit
def _bin(Xt, upper):
    """(F, N) raw -> (F, N) bins in 1..max_bin (0 is the empty missing bin):
    one more than the number of bounds below the value, by comparing with
    every bound (a binary search is a loop of gathers, slow on the TPU)."""
    mb = upper.shape[1]

    def one(col, ub):
        below = jnp.sum(ub[None, :] < col[:, None], axis=1, dtype=jnp.int32)
        return jnp.minimum(below, mb - 1) + 1
    return jax.lax.map(lambda a: one(*a), (Xt, upper))


@jax.jit
def _route_step(node, Xt, feat, thr, left, right):
    f = feat[node]
    x = jnp.sum(jnp.where(f[None, :] == jnp.arange(Xt.shape[0])[:, None],
                          Xt, 0.0), axis=0)
    nxt = jnp.where(x <= thr[node], left[node], right[node])
    return jnp.where(left[node] < 0, node, nxt)


def route(Xt, tree: Dict[str, np.ndarray]):
    """Leaf node id of every row, by this file's own walk: left where the
    raw value is <= the node's threshold."""
    depth = tree_depth(tree)
    node = jnp.zeros(Xt.shape[1], jnp.int32)
    args = [jnp.asarray(tree[k]) for k in ("split_feature", "threshold",
                                           "left_child", "right_child")]
    for _ in range(depth):
        node = _route_step(node, Xt, *args)
    return node


def tree_depth(tree: Dict[str, np.ndarray]) -> int:
    left, right = tree["left_child"], tree["right_child"]

    def d(j):
        return 0 if left[j] < 0 else 1 + max(d(int(left[j])), d(int(right[j])))
    return d(0)


def _round(x, low: Optional[str]):
    if low is None:
        return x
    # reduce_precision, not a pair of casts: XLA may drop a cast down and up
    # again as excess precision it is allowed to keep (it did, on the TPU)
    if low == "bfloat16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if low == "fp8":
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
    raise ValueError(f"unknown control precision {low!r}")


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "low"))
def _leaf_hists(bins, node, margin, y, *, n_nodes, n_bins, low):
    """Per chunk of rows, the histogram of (gradient, hessian, 1) over
    (leaf node, feature, bin): (chunks, F, n_bins, 3 * n_nodes) float32
    partial sums, summed in float64 by the caller."""
    F, N = bins.shape
    pad = (-N) % CHUNK
    p = jax.nn.sigmoid(margin)
    g = _round(p - y, low)
    h = _round(jnp.maximum(p * (1.0 - p), 1e-16), low)
    w = jnp.stack([g, h, jnp.ones_like(g)], -1)                       # (N, 3)
    w = jnp.pad(w, ((0, pad), (0, 0)))
    node = jnp.pad(node, (0, pad))
    bins = jnp.pad(bins, ((0, 0), (0, pad)))
    C = (N + pad) // CHUNK

    def chunk(args):
        b, nd, ww = args                                  # (F, c), (c,), (c, 3)
        lhs = (jax.nn.one_hot(nd, n_nodes, dtype=jnp.float32)[:, :, None]
               * ww[:, None, :]).reshape(CHUNK, n_nodes * 3)

        def feature(bf):
            return jnp.matmul(jax.nn.one_hot(bf, n_bins, dtype=jnp.float32).T,
                              lhs, precision=HIGHEST)
        return jax.lax.map(feature, b)                    # (F, n_bins, 3M)

    return jax.lax.map(chunk, (bins.reshape(F, C, CHUNK).transpose(1, 0, 2),
                               node.reshape(C, CHUNK),
                               w.reshape(C, CHUNK, 3)))


def _gains(hist, cfg):
    """hist (F, B, 3) float64 of one node -> gain (F, B) of sending bins
    <= b left, -inf where the configuration forbids the cut."""
    G, H, C = hist[..., 0], hist[..., 1], hist[..., 2]
    gl, hl, cl = np.cumsum(G, 1), np.cumsum(H, 1), np.cumsum(C, 1)
    sg, sh, sc = gl[0, -1], hl[0, -1], cl[0, -1]
    gr, hr, cr = sg - gl, sh - hl, sc - cl
    l2 = cfg["lambda_l2"]
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = gl * gl / (hl + l2) + gr * gr / (hr + l2) - sg * sg / (sh + l2)
    ok = ((cl >= cfg["min_data_in_leaf"]) & (cr >= cfg["min_data_in_leaf"])
          & (hl >= cfg["min_sum_hessian_in_leaf"])
          & (hr >= cfg["min_sum_hessian_in_leaf"]))
    ok[:, -1] = False
    return np.where(ok, gain, -np.inf)


def _candidates(root_gain: np.ndarray, cfg) -> np.ndarray:
    """(F, B) mask of the cuts the configuration lets a node of this tree
    consider."""
    F, B = root_gain.shape
    tl = cfg.get("two_level")
    if not tl or not tl.get("on"):
        return np.ones((F, B), bool)
    step = 1 << int(tl["shift"])
    coarse = np.zeros(B, bool)
    coarse[step - 1::step] = True
    per_feature = np.where(coarse[None, :], root_gain, -np.inf).max(1)
    top = np.argsort(-per_feature, kind="stable")[:int(tl["refine_features"])]
    mask = np.broadcast_to(coarse, (F, B)).copy()
    mask[top] = True
    return mask


def check_tree(cfg, bins, node, margin, y, tree: Dict[str, np.ndarray],
               low: Optional[str] = None) -> Dict[str, float]:
    M = len(tree["left_child"])
    B = cfg["max_bin"] + 1
    left, right = tree["left_child"], tree["right_child"]
    n_nodes = int(tree["num_nodes"])
    leaves = [j for j in range(n_nodes) if left[j] < 0]
    slot = np.zeros(M, np.int32)                 # node id -> its leaf's column
    slot[leaves] = np.arange(len(leaves))
    parts = _leaf_hists(bins, jnp.asarray(slot)[node], margin, y,
                        n_nodes=len(leaves), n_bins=B, low=low)
    by_leaf = np.asarray(parts, np.float64).sum(0)               # (F, B, 3L)
    F = by_leaf.shape[0]
    hist = np.zeros((M, F, B, 3))
    hist[leaves] = by_leaf.reshape(F, B, len(leaves), 3).transpose(2, 0, 1, 3)

    def fold(j):                      # a node's histogram: its leaves' sum
        if left[j] >= 0:
            hist[j] = fold(int(left[j])) + fold(int(right[j]))
        return hist[j]
    fold(0)
    mask = _candidates(_gains(hist[0], cfg), cfg)
    count_gap, leaf_ref, leaf_prog, splits = 0.0, [], [], []
    for j in range(n_nodes):
        tot = hist[j][0].sum(0)                                  # (3,)
        count_gap = max(count_gap, abs(float(tree["node_count"][j]) - tot[2]))
        if left[j] >= 0:
            # the best the allowed candidates offer, against the gain of
            # the program's own cut wherever it lies: which features the
            # root refines is decided among two dozen noise features by
            # rounding, so a fine cut outside the reference's eight is no
            # fault as long as it gains as much
            gains = _gains(hist[j], cfg)
            splits.append((np.where(mask, gains, -np.inf).max(),
                           gains[int(tree["split_feature"][j]),
                                 int(tree["split_bin"][j])]))
        else:
            leaf_ref.append(-cfg["learning_rate"] * tot[0]
                            / (tot[1] + cfg["lambda_l2"]))
            leaf_prog.append(float(tree["leaf_value"][j]))
    best, got = (np.asarray(x) for x in zip(*splits))
    with np.errstate(invalid="ignore"):
        gain_gap = max(0.0, ((best - got)
                             / np.maximum(best, np.median(best))).max())
    if not np.isfinite(gain_gap):          # a cut the configuration forbids
        gain_gap = float("inf")
    leaf_ref, leaf_prog = np.asarray(leaf_ref), np.asarray(leaf_prog)
    scale = np.maximum(np.abs(leaf_ref), np.median(np.abs(leaf_ref)))
    return {"split_gain_gap": float(gain_gap),
            "leaf_value_gap": float((np.abs(leaf_prog - leaf_ref) / scale).max()),
            "node_count_gap": float(count_gap),
            "leaves": len(leaf_ref), "leaf_ref": leaf_ref}


def initial_margin(y: np.ndarray) -> float:
    mean = min(max(float(np.mean(y)), 1e-6), 1 - 1e-6)
    return float(np.log(mean / (1 - mean)))


def check_fit(cfg: Dict[str, Any], X: np.ndarray, y: np.ndarray,
              trees: Sequence[Dict[str, np.ndarray]], which: Sequence[int],
              low: Optional[str] = None) -> Dict[str, Any]:
    """Follow the program's trees ``0 .. max(which)`` with this file's own
    margins, and check the trees in ``which``.  The widest of each gap."""
    Xt = jnp.asarray(np.ascontiguousarray(X.T))
    yd = jnp.asarray(y, jnp.float32)
    bins = _bin(Xt, jnp.asarray(bin_bounds(cfg, X)))
    margin = jnp.full(X.shape[0], initial_margin(y), jnp.float32)
    out = {"split_gain_gap": 0.0, "leaf_value_gap": 0.0, "node_count_gap": 0.0,
           "min_leaves": 10 ** 9, "per_tree": {}}
    for t in range(max(which) + 1):
        node = route(Xt, trees[t])
        if t in which:
            r = check_tree(cfg, bins, node, margin, yd, trees[t], low)
            out["per_tree"][t] = r
            for k in ("split_gain_gap", "leaf_value_gap", "node_count_gap"):
                out[k] = max(out[k], r[k])
            out["min_leaves"] = min(out["min_leaves"], r["leaves"])
        margin = margin + jnp.asarray(trees[t]["leaf_value"], jnp.float32)[node]
    return out


def walk_margin(X: np.ndarray, y_mean_margin: float,
                trees: Sequence[Dict[str, np.ndarray]]) -> np.ndarray:
    """This file's prediction of rows ``X`` from every tree, float64 sum."""
    Xt = jnp.asarray(np.ascontiguousarray(X.T))
    total = np.full(X.shape[0], y_mean_margin, np.float64)
    for tree in trees:
        node = np.asarray(route(Xt, tree))
        total += np.asarray(tree["leaf_value"], np.float64)[node]
    return total
