"""Operations and bytes of latent attention (``kv_lora_rank`` in the
configuration: every layer ``latent_attention``), from the spans the engine
writes: ``engine.step``'s ``kv_span_sum`` (the live positions of the slots in
use) and ``engine.admit``'s ``prompt_tokens``, ``reused_tokens``,
``latent_prefill_form`` and ``latent_rows_expanded``.

Only needed work is counted: a latent row at its ``kv_lora_rank +
qk_rope_head_dim`` values (576, not the 640 lanes the cache holds), each row
once however many query blocks read it, the real tokens of a pass and not its
bucket, the keys a causal query sees.  So a share cannot pass 100% by
construction.  A configuration without ``kv_lora_rank`` is not this module's:
its readers return ``None`` there.
"""

from __future__ import annotations

from typing import Any, Dict


def applies(cfg: Dict[str, Any]) -> bool:
    return bool(cfg.get("kv_lora_rank"))


def _d(cfg: Dict[str, Any]):
    return (cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def decode_work(cfg: Dict[str, Any], span_sum: float, itemsize: int = 2
                ) -> Dict[str, float]:
    """One decode step's latent attention over every layer, absorbed: each
    live row read once (``span_sum`` rows a layer), and for each row and
    head a score over the row's ``r + dr`` values and a value product over
    its ``r``."""
    L, H, r, _, dr, _ = _d(cfg)
    return {"bytes": L * span_sum * (r + dr) * itemsize,
            "ops": 2.0 * L * span_sum * H * ((r + dr) + r)}


def _causal_pairs(start: int, n: int) -> float:
    """(query, key) pairs of ``n`` queries at ``start ..`` over every earlier
    key and their own."""
    return n * start + n * (n + 1) / 2.0


def prefill_work(cfg: Dict[str, Any], attrs: Dict[str, Any],
                 itemsize: int = 2) -> Dict[str, float]:
    """The attention of one prefill pass over every layer, by its form
    (``engine.admit``'s attributes), the expansion of rows to heads apart
    (``expand_ops``): ``cold`` and ``expanded`` score over keys ``dn + dr``
    wide and take values ``dv`` wide a head; ``absorbed`` scores over the
    latent's ``r + dr`` and takes its ``r`` a head, and folds the query and
    unfolds the result (``2 x r x (dn + dv)`` a query and head).  Bytes: the
    rows or expanded keys and values the kernel needs, each once."""
    L, H, r, dn, dr, dv = _d(cfg)
    start = int(attrs.get("reused_tokens", 0))
    n = int(attrs["prompt_tokens"]) - start
    form = attrs["latent_prefill_form"]
    pairs = _causal_pairs(start, n)
    keys = start + n
    if form == "absorbed":
        ops = 2.0 * L * H * (pairs * ((r + dr) + r) + n * r * (dn + dv))
        nbytes = L * keys * (r + dr + r) * itemsize
    else:
        ops = 2.0 * L * H * pairs * (dn + dr + dv)
        nbytes = L * keys * H * (dn + dr + dv) * itemsize
    return {"bytes": nbytes, "ops": ops,
            "expand_ops": 2.0 * int(attrs.get("latent_rows_expanded", 0))
            * r * H * (dn + dv)}
