"""``serve_mfu`` for a decoder with expert layers, as one chip holds it: the
whole serving step's share of the chip's bf16 peak.  Counted: 2 x the matrix
parameters every token touches in the layers held (attention, router, the
shared experts: ``work_moe.token_flops``) for every token processed in the traced part (a traced ``engine.admit``'s
``prompt_tokens`` less its ``reused_tokens``: a reused prefix is nobody's
work; a traced ``engine.step``'s ``tokens``), ``6 x hidden x width`` for every (token, expert)
pair computed HERE (``expert_pairs_held`` of the traced ``engine.step`` and
``engine.admit`` spans: the pairs of absent experts are nobody's work on this
chip), and the output head once a produced token.  Attention products are
left out, so it under-counts.  A program whose spans carry no pair count
gives nothing to read."""
from benchmark import work_moe


def read(facts, cell, peak, work, **_):
    span = facts.get("trace_host")
    if not span or span[0] is None or "router_experts" not in cell.config:
        return None
    a, b = span
    c = cell.config
    steps = work_moe.traced_spans("engine.step", facts, "expert_pairs_held")
    admits = work_moe.traced_spans("engine.admit", facts, "expert_pairs_held")
    if not steps and not admits:
        return None
    pairs = sum(s.attrs["expert_pairs_held"] for s in steps + admits)
    decoded = sum(s.attrs.get("tokens", 0) for s in steps)
    prefilled = sum(s.attrs["prompt_tokens"] - s.attrs["reused_tokens"]
                    for s in admits)
    flops = (decoded + prefilled) * work_moe.token_flops(c) \
        + pairs * work_moe.pair_flops(c) \
        + (decoded + len(admits)) * work.lm_head_flops(c["hidden_size"],
                                                       c["vocab_size"])
    return 100.0 * flops / ((b - a) * peak["flops_bf16"])
