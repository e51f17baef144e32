"""Operations and bytes of a decoder with expert layers and window layers,
as ONE chip of a deployment holds it: beside ``work.py``, which counts a
dense Llama layer.

The expert layer's grouped product (``expert_ffn``) has to read the three
matrices of every held expert that has a pair, once, and each pair's rows;
a window layer's attention has to read ``min(span, window)`` keys and values
a slot.  Only needed work is counted: no tile's padding rows, no weights of
an expert without a pair, so a share cannot pass 100% by construction.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmark import span_read, work
from benchmark import trace_reduce as tr


# -- counts -------------------------------------------------------------------

def expert_params(hidden: int, width: int) -> int:
    """Matrix parameters of one expert: gate, up, down."""
    return 3 * hidden * width


def attention_params(hidden: int, heads: int, kv_heads: int,
                     head_dim: int) -> int:
    """q, k, v, o: ``work``'s decoder layer without its feed-forward."""
    return work.decoder_layer_params(hidden, heads, kv_heads, head_dim, 0)


def layer_params(cfg: Dict[str, Any], experts: int) -> int:
    """One layer with ``experts`` routed experts: attention, the router
    over all ``router_experts``, the shared experts, the routed ones."""
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    return attention_params(h, cfg["num_attention_heads"],
                            cfg["num_key_value_heads"], cfg["head_dim"]) \
        + h * cfg["router_experts"] \
        + (cfg["num_shared_experts"] + experts) * expert_params(h, w)


def token_flops(cfg: Dict[str, Any]) -> float:
    """2 x the matrix parameters EVERY token touches in the layers held:
    attention, router and shared experts (the routed experts are counted by
    the pair: :func:`pair_flops`); attention products are left out."""
    return 2.0 * cfg["num_hidden_layers"] * layer_params(cfg, 0)


def pair_flops(cfg: Dict[str, Any]) -> float:
    """One (token, expert) pair through one expert: 6 x hidden x width."""
    return 2.0 * expert_params(cfg["hidden_size"], cfg["intermediate_size"])


def expert_bytes(cfg: Dict[str, Any], touched: float, pairs: float,
                 itemsize: int = 2) -> float:
    """What the grouped product has to move for ``pairs`` pairs over
    ``touched`` experts (both summed over layers): each touched expert's
    three matrices once; a pair's input row, its gated row written and read
    again, its result in float32."""
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    return touched * expert_params(h, w) * itemsize \
        + pairs * (h * itemsize + 2 * w * itemsize + 4 * h)


def expert_work(cfg: Dict[str, Any], touched: float, pairs: float
                ) -> Dict[str, float]:
    return {"bytes": expert_bytes(cfg, touched, pairs),
            "ops": pairs * pair_flops(cfg)}


def window_layers(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(full-attention layers, window layers) of the layers held."""
    kinds = cfg.get("layer_types") or \
        ["full_attention"] * cfg["num_hidden_layers"]
    n_win = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - n_win, n_win


def window_kv_bytes(cfg: Dict[str, Any], span_sum: float,
                    window_span_sum: float, itemsize: int = 2) -> float:
    """K and V bytes ONE decode step has to read: every live key and value
    of every slot in use once a full layer (``span_sum``), the last
    ``min(span, window)`` once a window layer (``window_span_sum``)."""
    full, win = window_layers(cfg)
    row = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return row * (full * span_sum + win * window_span_sum)


# -- the trace: a kernel's time inside one program ----------------------------------

def kernel_seconds_in(device: Dict[str, Any], base: str, program: str
                      ) -> Tuple[float, int]:
    """(seconds, calls) of the operations named ``base`` that ran inside the
    programs whose name contains ``program``: a kernel that two programs
    call (``expert_ffn`` in decode and in prefill) is told apart by where
    its events lie."""
    spans = tr.union(iv for name, rec in device["modules"].items()
                     if program in name for iv in rec["intervals"])
    ns, n = 0.0, 0
    for rec in device["ops"].values():
        if not rec["base"].startswith(base):
            continue
        for s, e in rec["intervals"]:
            inside = (e - s) - tr.total(tr.subtract([(s, e)], spans))
            if inside > 0:
                ns += inside
                n += 1
    return ns / 1e9, n


def traced_spans(name: str, facts: Dict[str, Any], attr: str) -> list:
    """The program's ``name`` spans of the traced part that carry ``attr``."""
    found = span_read.started_in(span_read.spans(name),
                                 facts.get("trace_host"))
    return [s for s in found if attr in s.attrs]
