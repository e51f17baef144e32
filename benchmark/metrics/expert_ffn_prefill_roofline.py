"""The expert layer's grouped product in prefill, its share of its roofline.
For the admissions traced (the ``engine.admit`` spans that carry
``expert_pairs_held``) the least time is the larger of the bytes
(``work_moe.expert_bytes``: a prefill's counts ride its logits, and a span
carries the pairs alone, so every held expert of every layer is taken as
touched, which a tail of 32 tokens already makes nearly true) over the HBM
peak and ``6 x hidden x width`` operations a pair over the bf16 peak; summed
over those admissions, over the device time of ``expert_ffn`` inside
``_prefill_slot_jit``.  The admissions spanned and the prefills traced
differ by one at the edges, so the mean of an admission is scaled to the
prefill programs the trace holds.  A program without the kernel or the count
gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work, work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    admits = work_moe.traced_spans("engine.admit", facts, "expert_pairs_held")
    dev = tr.fullest(trace)
    secs, calls = work_moe.kernel_seconds_in(dev, "expert_ffn",
                                             "_prefill_slot_jit")
    _, runs = tr.module_seconds(dev, "_prefill_slot_jit")
    if not admits or not calls or not runs or secs <= 0:
        return None
    touched = c["num_hidden_layers"] * c["num_experts"]
    least = sum(work.least_seconds(
        work_moe.expert_work(c, touched, s.attrs["expert_pairs_held"]), peak,
        ops_key="ops") for s in admits) / len(admits)
    return 100.0 * least * runs / secs
