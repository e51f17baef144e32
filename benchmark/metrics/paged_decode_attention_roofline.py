"""The paged decode kernel's share of its roofline: the K and V bytes its
calls need (every live key and value of every slot in use once per layer:
``work.paged_kv_bytes`` with tile 1, the spans sampled at each traced step)
over the HBM peak, over the kernel's device time in the trace.  Bound by
memory.  The steps sampled and the steps traced differ by a step at the
edges, so the bytes are scaled to the calls the trace holds."""
from benchmark import trace_reduce as tr


def read(trace, facts, cell, peak, work, **_):
    steps = facts.get("steps")
    secs, calls = tr.op_seconds(tr.fullest(trace), ["paged_decode_attention"],
                                "self_ns")
    if not steps or not calls or secs <= 0:
        return None
    c = cell.config
    layers = c["num_hidden_layers"]
    per_layer = sum(work.paged_kv_bytes([span], c["num_key_value_heads"],
                                        c["head_dim"], 2) for _, span in steps)
    needed = per_layer * layers * (calls / layers) / len(steps)
    return 100.0 * (needed / peak["hbm_bytes_per_s"]) / secs
