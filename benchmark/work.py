"""Operations and bytes that the work needs, computed from shapes.

These are the yardstick's own counts: a roofline share divides the least
time the chip could take for the work counted HERE by the time measured in
the trace.  Only required work is counted (no padding, no recomputation,
no work on inactive slots), so a share cannot pass 100% by construction.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np


# -- decoder-only transformer (llm_serve) ------------------------------------

def decoder_layer_params(hidden: int, heads: int, kv_heads: int, head_dim: int,
                         ffn: int) -> int:
    """Matrix parameters of one decoder layer: q, k, v, o and the three
    SwiGLU projections (norm scales are not matrix work)."""
    attn = hidden * heads * head_dim * 2 + hidden * kv_heads * head_dim * 2
    return attn + 3 * hidden * ffn


def decoder_token_flops(layers: int, hidden: int, heads: int, kv_heads: int,
                        head_dim: int, ffn: int) -> float:
    """2 x matrix parameters touched by one token in the layers (the
    attention products over the context are left out: an under-count)."""
    return 2.0 * layers * decoder_layer_params(hidden, heads, kv_heads,
                                               head_dim, ffn)


def lm_head_flops(hidden: int, vocab: int) -> float:
    """2 x parameters of the output head, needed once per produced token."""
    return 2.0 * hidden * vocab


def paged_kv_bytes(spans: Iterable[int], kv_heads: int, head_dim: int,
                   itemsize: int, layers: int = 1, tile: int = 1) -> int:
    """K and V bytes one decode step has to read for live ``spans``.

    ``tile=1`` is what the attention needs (every live key and value
    once).  With the kernel's key tile it is what the kernel's grid DMAs
    (``ceil(span / tile)`` whole tiles per slot), the same arithmetic as
    the program's ``pallas_attn.paged_read_bytes``."""
    spans = np.maximum(np.asarray(list(spans), np.float64), 1.0)
    tiles = np.ceil(spans / tile).astype(np.int64)
    return int(layers * 2 * tiles.sum() * tile * kv_heads * head_dim
               * itemsize)


# -- encoder fine-tuning (dl_train) ------------------------------------------

def encoder_layer_params(hidden: int, ffn: int) -> int:
    return 4 * hidden * hidden + 2 * hidden * ffn


def train_token_flops(layers: int, hidden: int, ffn: int) -> float:
    """6 x non-embedding matrix parameters per token (forward 2, backward
    4); recomputed work is not counted."""
    return 6.0 * layers * encoder_layer_params(hidden, ffn)


# -- histogram boosting (gbdt_fit) -------------------------------------------

def tree_levels(num_leaves: int) -> int:
    """Depth-wise waves that grow ``num_leaves`` leaves: ceil(log2)."""
    return int(np.ceil(np.log2(max(num_leaves, 2))))


def hist_pass_work(rows: int, features: int, bins: int, bin_bytes: int = 4,
                   value_bytes: int = 8, channels: int = 3) -> Dict[str, float]:
    """One level's histogram pass over every row: read each row's bin of
    each feature once and its gradient, hessian pair once; add each row's
    ``channels`` values (gradient, hessian, count) into one bin per
    feature.  As a product with a one-hot of ``bins`` columns that is
    ``2 * rows * features * bins * channels`` operations, which is how
    the MXU does it; the adds alone are ``rows * features * channels``.
    Both are given; the roofline uses bytes and the adds (the least)."""
    return {
        "bytes": float(rows) * features * bin_bytes + float(rows) * value_bytes,
        "ops_min": float(rows) * features * channels,
        "ops_onehot": 2.0 * rows * features * bins * channels,
    }


def boost_iteration_work(rows: int, features: int, bins: int,
                         num_leaves: int) -> Dict[str, float]:
    """Least work of one boosting iteration: gradients (read margin and
    label, write gradient and hessian: 16 bytes a row), one histogram
    pass per level, and the margin update (8 bytes a row)."""
    lv = tree_levels(num_leaves)
    one = hist_pass_work(rows, features, bins)
    return {
        "levels": lv,
        "bytes": lv * one["bytes"] + 24.0 * rows,
        "ops_min": lv * one["ops_min"] + 10.0 * rows,
        "ops_onehot": lv * one["ops_onehot"],
    }


def least_seconds(work: Dict[str, float], peak: Dict[str, float],
                  ops_key: str = "ops_min", ops_peak: str = "flops_bf16") -> float:
    """The roofline: the larger of bytes over the memory peak and
    operations over the arithmetic peak."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work[ops_key] / peak[ops_peak])
