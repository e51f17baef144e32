"""The whole serving step's share of the chip's bf16 peak: 2 x matrix
parameters touched per token (the layers for every token whose processing
finished in the traced part of the window, the output head once per produced
token), over the seconds traced.  Attention products are left out, so it
under-counts."""
import numpy as np


def read(facts, cell, peak, work, **_):
    span = facts.get("trace_host")
    if not span or span[0] is None:
        return None
    a, b = span
    c = cell.config
    all_tok = out_tok = 0
    for r in facts["records"]:
        if r["error"] or not r["times"]:
            continue
        t = np.asarray(r["times"])
        n = int(((t >= a) & (t < b)).sum())
        out_tok += n
        all_tok += n + (r["prompt_len"] if a <= t[0] < b else 0)
    if not all_tok:
        return None
    flops = all_tok * work.decoder_token_flops(
        c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["intermediate_size"]) \
        + out_tok * work.lm_head_flops(c["hidden_size"], c["vocab_size"])
    return 100.0 * flops / ((b - a) * peak["flops_bf16"])
