"""``serve_mfu`` for a decoder whose layers differ in kind, as one chip holds
it: the whole serving step's share of the chip's bf16 peak.  Counted: 2 x the
matrix parameters every token touches, each layer as ITS kind (attention at
the kind's K/V heads and the key's and value's widths, then the dense
feed-forward or the router: ``work_kinds.token_flops``), for every token
processed in the traced part (a traced ``engine.admit``'s ``prompt_tokens``
less its ``reused_tokens``; a traced ``engine.step``'s ``tokens``);
``6 x hidden x moe_intermediate_size`` for every (token, expert) pair computed
HERE (``expert_pairs_held`` of the traced spans: the pairs of absent experts
are nobody's work on this chip); and the output head once a produced token.
Attention products are left out, so it under-counts.  A configuration without
layer kinds, or a program whose spans carry no pair count, gives nothing to
read."""
from benchmark import work_kinds, work_moe


def read(facts, cell, peak, **_):
    span = facts.get("trace_host")
    c = cell.config
    if not span or span[0] is None or not work_kinds.applies(c):
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
    flops = (decoded + prefilled) * work_kinds.token_flops(c) \
        + pairs * work_kinds.pair_flops(c) \
        + (decoded + len(admits)) * work_kinds.head_flops(c)
    return 100.0 * flops / ((b - a) * peak["flops_bf16"])
