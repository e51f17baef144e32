"""Operations and bytes of a decoder whose layers differ in KIND, as ONE chip
of a deployment holds it: attention with its own K/V heads, key width and
value width on full and on window layers (``hybrid_layer_pattern``), a dense
feed-forward on some layers and routed experts of another width on the others
(``moe_layer_freq``), a router over ``router_experts`` of which
``n_routed_experts`` are held here.  Beside ``work.py`` (a dense Llama layer)
and ``work_moe.py`` (one kind of attention, every layer experts, whose
``intermediate_size`` is the expert's width and here is the dense layer's).

Only needed work is counted: a key at its published width (192, not the 256
lanes a padded row would take), ``min(span, window)`` keys a window layer, no
tile's padding rows, no weights of an expert without a pair, no pair of an
absent expert.  So a share cannot pass 100% by construction.  A configuration
without ``hybrid_layer_pattern`` is not this module's: its readers return
``None`` there.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark import work, work_moe


def applies(cfg: Dict[str, Any]) -> bool:
    return "hybrid_layer_pattern" in cfg and "moe_layer_freq" in cfg


def layers(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Each held layer: window or full with that kind's K/V heads, dense or
    experts."""
    out = []
    for i in range(cfg["num_hidden_layers"]):
        sliding = bool(cfg["hybrid_layer_pattern"][i])
        out.append({"sliding": sliding, "moe": bool(cfg["moe_layer_freq"][i]),
                    "kv": cfg["swa_num_key_value_heads" if sliding
                              else "num_key_value_heads"]})
    return out


def attention_params(cfg: Dict[str, Any], kv_heads: int) -> int:
    """q and k at the key's width, v and o at the value's."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    d, dv = cfg["head_dim"], cfg["v_head_dim"]
    return h * H * d + h * kv_heads * d + h * kv_heads * dv + H * dv * h


def _expert_view(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration as ``work_moe`` reads an expert's width."""
    return dict(cfg, intermediate_size=cfg["moe_intermediate_size"])


def expert_params(cfg: Dict[str, Any]) -> int:
    return work_moe.expert_params(cfg["hidden_size"],
                                  cfg["moe_intermediate_size"])


def expert_layers(cfg: Dict[str, Any]) -> int:
    return sum(layer["moe"] for layer in layers(cfg))


def token_params(cfg: Dict[str, Any]) -> int:
    """Matrix parameters EVERY token touches in the layers held: each
    layer's attention at its kind's heads, then the dense feed-forward or
    the router (routed experts are counted by the pair)."""
    h = cfg["hidden_size"]
    return sum(attention_params(cfg, layer["kv"])
               + (h * cfg["router_experts"] if layer["moe"]
                  else 3 * h * cfg["intermediate_size"])
               for layer in layers(cfg))


def token_flops(cfg: Dict[str, Any]) -> float:
    """2 x :func:`token_params`; attention products are left out."""
    return 2.0 * token_params(cfg)


def pair_flops(cfg: Dict[str, Any]) -> float:
    """One (token, expert) pair through one expert: 6 x hidden x width."""
    return work_moe.pair_flops(_expert_view(cfg))


def expert_bytes(cfg: Dict[str, Any], touched: float, pairs: float,
                 itemsize: int = 2) -> float:
    """What the grouped product has to move for ``pairs`` pairs over
    ``touched`` experts (both summed over layers): each touched expert's
    three matrices once; a pair's input row, its gated row written and read
    again, its result in float32: ``work_moe``'s count at the expert's own
    width."""
    return work_moe.expert_bytes(_expert_view(cfg), touched, pairs, itemsize)


def expert_work(cfg: Dict[str, Any], touched: float, pairs: float
                ) -> Dict[str, float]:
    return {"bytes": expert_bytes(cfg, touched, pairs),
            "ops": pairs * pair_flops(cfg)}


def kv_row_bytes(cfg: Dict[str, Any], kv_heads: int, itemsize: int = 2) -> int:
    """K and V of one position of one layer, unpadded."""
    return kv_heads * (cfg["head_dim"] + cfg["v_head_dim"]) * itemsize


def kv_bytes(cfg: Dict[str, Any], span_sum: float, window_span_sum: float,
             itemsize: int = 2) -> float:
    """K and V bytes ONE decode step has to read: every live key and value
    of every slot in use once a full layer (``span_sum``), the last
    ``min(span, window)`` once a window layer (``window_span_sum``), each at
    its kind's heads and the published widths."""
    return sum(kv_row_bytes(cfg, layer["kv"], itemsize)
               * (window_span_sum if layer["sliding"] else span_sum)
               for layer in layers(cfg))


def head_flops(cfg: Dict[str, Any]) -> float:
    return work.lm_head_flops(cfg["hidden_size"], cfg["vocab_size"])
