"""Operations and bytes of a decoder laid out by ``full_attention_interval``
(Qwen3-Next's keys): gated DeltaNet layers with fewer key heads than value
heads, a gated full-attention layer every ``interval``-th, and on every layer
small routed experts (``moe_intermediate_size``) beside one shared expert of
its own width behind a sigmoid gate, as ONE chip of a deployment holds it
(``num_experts`` of the router's ``router_experts``).  Beside ``work_gdn``
(equal key and value heads, a dense feed-forward) and ``work_kinds`` (layer
kinds by ``hybrid_layer_pattern``), whose readers return ``None`` here.

Only needed work is counted: the recurrent state of a slot in use once in and
once out, q and k at the key heads, no tile's padding rows, no weights of an
expert without a pair, no pair of an absent expert.  So a share cannot pass
100% by construction.  A configuration without ``full_attention_interval``
is not this module's: its readers return ``None`` there.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark import work_kinds, work_moe

FLOAT32 = 4


def applies(cfg: Dict[str, Any]) -> bool:
    return "full_attention_interval" in cfg and "linear_num_key_heads" in cfg


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    """Layer ``i`` is full attention iff ``(i + 1) % interval == 0``."""
    every = int(cfg["full_attention_interval"])
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def linear_dims(cfg: Dict[str, Any]):
    """(value heads, key heads, d_k, d_v) of the linear layers."""
    return (cfg["linear_num_value_heads"], cfg["linear_num_key_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def linear_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of a linear layer's mixer: q and k at the key heads
    and v and the output gate z at the value heads (``in_proj_qkvz``), b and
    a a value head (``in_proj_ba``), ``out_proj``.  The convolution, the
    norms and the per-head gate constants are not matrix work."""
    nv, nk, dk, dv = linear_dims(cfg)
    h = cfg["hidden_size"]
    return h * (2 * nk * dk + 2 * nv * dv) + h * 2 * nv + nv * dv * h


def full_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters of the full layer's mixer: q with its gate (two
    head widths a query head), k and v at the K/V heads, o."""
    h, H, KV, D = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    return h * H * 2 * D + 2 * h * KV * D + H * D * h


def moe_shared_params(cfg: Dict[str, Any]) -> int:
    """What every token of an expert layer touches besides its routed
    pairs: the router over all ``router_experts``, the shared expert at its
    own width and its gate."""
    h = cfg["hidden_size"]
    return h * cfg["router_experts"] \
        + work_moe.expert_params(h, cfg["shared_expert_intermediate_size"]) + h


def expert_layers(cfg: Dict[str, Any]) -> int:
    """Layers with experts: ``(i + 1) % decoder_sparse_step == 0`` and not in
    ``mlp_only_layers``."""
    step = int(cfg.get("decoder_sparse_step") or 1)
    dense = set(cfg.get("mlp_only_layers") or ())
    return sum((i + 1) % step == 0 and i not in dense
               for i in range(cfg["num_hidden_layers"]))


def token_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters EVERY token touches in the layers held, each layer
    as its kind (routed experts are counted by the pair:
    :func:`pair_flops`)."""
    mixers = sum(linear_params(cfg) if k == "linear_attention"
                 else full_params(cfg) for k in layer_kinds(cfg))
    return mixers + expert_layers(cfg) * moe_shared_params(cfg)


def recurrence_flops(cfg: Dict[str, Any]) -> float:
    """One token through the recurrence of every linear layer: three
    products over each value head's ``d_k x d_v`` state, two operations an
    element (``work_gdn``'s count)."""
    nv, _, dk, dv = linear_dims(cfg)
    n = layer_kinds(cfg).count("linear_attention")
    return float(n * nv * 6 * dk * dv)


def token_flops(cfg: Dict[str, Any]) -> float:
    """2 x :func:`token_params` plus the recurrence; attention's products
    over the context are left out (an under-count)."""
    return 2.0 * token_params(cfg) + recurrence_flops(cfg)


#: a pair, the grouped product's bytes and the head: ``work_kinds``' counts
#: at ``moe_intermediate_size``, which read nothing of the layer kinds
pair_flops = work_kinds.pair_flops
expert_bytes = work_kinds.expert_bytes
head_flops = work_kinds.head_flops


def gdn_decode_call_bytes(cfg: Dict[str, Any], slots: int) -> int:
    """What one linear layer's decode step has to move for ``slots`` slots
    in use: each one's state (value heads, float32) read once and written
    once, its token's q and k at the key heads, v and the output at the
    value heads, and the two gates a value head, float32."""
    nv, nk, dk, dv = linear_dims(cfg)
    state = FLOAT32 * nv * dk * dv
    io = FLOAT32 * (2 * nk * dk + 2 * nv * dv + 2 * nv)
    return slots * (2 * state + io)
