"""The prefill kernel of the gated delta rule, its share of its roofline.
For the admissions traced (the requests whose first token arrived in the
traced part: each prefilled its whole prompt, one kernel call a linear layer)
the least time of a call is the larger of its bytes (state in and out once
plus the real tokens' inputs and outputs) over the HBM peak and its
operations (``6 d_k d_v`` a token a head) over the bf16 peak
(``work_gdn.prefill_call_work``); the mean over those admissions, times the
calls the trace holds, over the device time of ``gated_delta_prefill``.  The
same work whatever the kernel's body does.  A program without the kernel
gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_gdn


def read(trace, facts, cell, peak, work, **_):
    span = facts.get("trace_host")
    secs, calls = tr.op_seconds(tr.fullest(trace), ["gated_delta_prefill"],
                                "self_ns")
    if not span or span[0] is None or not calls or secs <= 0:
        return None
    a, b = span
    prompts = [r["prompt_len"] for r in facts.get("records", ())
               if not r["error"] and r["times"] and a <= r["times"][0] < b]
    if not prompts:
        return None
    dims = work_gdn.linear_dims(cell.config)
    least = sum(work.least_seconds(work_gdn.prefill_call_work(n, *dims), peak,
                                   ops_key="ops") for n in prompts)
    return 100.0 * (least / len(prompts)) * calls / secs
