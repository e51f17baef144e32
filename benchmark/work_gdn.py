"""Operations and bytes of a decoder whose layers are of two kinds: full
attention, and linear attention by the gated delta rule (``layer_types`` of
the configuration).  The counts of ``work.py`` stay as they are; these are
the ones it lacks.  Only required work is counted (live slots, real tokens,
the state once in and once out), so a share cannot pass 100% by construction.

Per token and head of ``d_k`` by ``d_v`` the recurrence is

    S <- alpha (S - beta k (k^T S)) + beta k v^T,    o = S^T q

three products over the state (``k^T S``, the rank-one update, ``S^T q``) at
two operations an element: ``6 d_k d_v``.  Its inputs and output are what
the layer computes in float32 (after the convolution, SiLU and the norms):
q and k (``d_k`` each), v and o (``d_v`` each), the two gates.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import work

FLOAT32 = 4


def linear_dims(cfg: Dict[str, Any]):
    """(heads, d_k, d_v) of the configuration's linear layers."""
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def linear_layer_params(hidden: int, heads: int, d_k: int, d_v: int,
                        ffn: int) -> int:
    """Matrix parameters of one linear-attention layer: q and k
    (``hidden x heads d_k`` each), v, the output gate and o (``hidden x
    heads d_v`` each), the two gates' projections (``hidden x heads``
    each) and the three SwiGLU projections.  The depthwise convolution, the
    norms and the per-head gate constants are not matrix work."""
    return hidden * heads * (2 * d_k + 3 * d_v) + 2 * hidden * heads \
        + 3 * hidden * ffn


def hybrid_token_flops(cfg: Dict[str, Any]) -> float:
    """2 x matrix parameters one token touches in the layers, each layer
    counted as its kind in ``layer_types`` (the attention products over the
    context and the recurrence are left out: an under-count)."""
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    full = work.decoder_layer_params(
        h, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], ffn)
    linear = linear_layer_params(h, *linear_dims(cfg), ffn)
    kinds = cfg["layer_types"]
    n_linear = sum(k == "linear_attention" for k in kinds)
    return 2.0 * (n_linear * linear + (len(kinds) - n_linear) * full)


def token_io_bytes(heads: int, d_k: int, d_v: int) -> int:
    """One token's q, k, v, two gates and output of one layer, float32."""
    return FLOAT32 * heads * (2 * d_k + 2 * d_v + 2)


def state_bytes(heads: int, d_k: int, d_v: int) -> int:
    """One slot's recurrent state of one layer, float32."""
    return FLOAT32 * heads * d_k * d_v


def decode_call_bytes(slots: int, heads: int, d_k: int, d_v: int) -> int:
    """What one layer's decode step has to move for ``slots`` slots in use:
    each one's state read once and written once, and its token's inputs and
    output."""
    return slots * (2 * state_bytes(heads, d_k, d_v)
                    + token_io_bytes(heads, d_k, d_v))


def prefill_call_work(tokens: int, heads: int, d_k: int, d_v: int
                      ) -> Dict[str, float]:
    """One layer's prefill of ``tokens`` real tokens of one slot: the state
    in and out once plus every token's inputs and output; ``6 d_k d_v``
    operations a token a head.  The same whatever the kernel's body does
    (token by token or in chunks)."""
    return {"bytes": float(2 * state_bytes(heads, d_k, d_v)
                           + tokens * token_io_bytes(heads, d_k, d_v)),
            "ops": float(tokens) * heads * 6 * d_k * d_v}
