"""``serve_mfu`` for a decoder laid out by ``full_attention_interval``, as one
chip holds it: the whole serving step's share of the chip's bf16 peak.
Counted: for every token processed in the traced part (a traced
``engine.admit``'s ``prompt_tokens`` less its ``reused_tokens``; a traced
``engine.step``'s ``tokens``) 2 x the matrix parameters it touches, each
layer as ITS kind (a linear layer's projections at the key and value heads,
the full layer's q with its gate, k, v and o, the router, the shared expert
at its own width and its gate) and the recurrence
(``work_qwen3_next.token_flops``); ``6 x hidden x moe_intermediate_size`` for
every (token, expert) pair computed HERE (``expert_pairs_held`` of the traced
spans); and the output head once a produced token.  Attention's products over
the context are left out, so it under-counts.  A configuration without
``full_attention_interval``, or a program whose spans carry no pair count,
gives nothing to read."""
from benchmark import work_moe
from benchmark import work_qwen3_next as wq


def read(facts, cell, peak, **_):
    span = facts.get("trace_host")
    c = cell.config
    if not span or span[0] is None or not wq.applies(c):
        return None
    a, b = span
    steps = work_moe.traced_spans("engine.step", facts, "expert_pairs_held")
    admits = work_moe.traced_spans("engine.admit", facts, "expert_pairs_held")
    if not steps and not admits:
        return None
    pairs = sum(s.attrs["expert_pairs_held"] for s in steps + admits)
    decoded = sum(s.attrs.get("tokens", 0) for s in steps)
    prefilled = sum(s.attrs["prompt_tokens"] - s.attrs["reused_tokens"]
                    for s in admits)
    flops = (decoded + prefilled) * wq.token_flops(c) \
        + pairs * wq.pair_flops(c) \
        + (decoded + len(admits)) * wq.head_flops(c)
    return 100.0 * flops / ((b - a) * peak["flops_bf16"])
