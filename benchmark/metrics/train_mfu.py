"""The whole training step's share of the chips' bf16 peak: 6 x
non-embedding matrix parameters x tokens per second over the traced steps
(host clock around them), over chips x peak.  No recomputed work counted;
attention products left out, so it under-counts."""


def read(facts, cell, peak, work, chips, **_):
    span, n = facts.get("trace_host"), facts.get("traced_steps")
    if not span or not n:
        return None
    c = cell.config
    tokens = n * c["global_batch"] * c["sequence_length"]
    flops = tokens * work.train_token_flops(
        c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"])
    return 100.0 * flops / ((span[1] - span[0]) * chips * peak["flops_bf16"])
