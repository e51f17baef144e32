"""The prefill kernel's share of its roofline in the admissions traced of a
model with latent attention: for the ``engine.admit`` spans whose attention
took the kernel (``prefill_attention`` ``tiled``), the larger of the bytes and
the products of each pass's attention in its form (``work_latent
.prefill_work``) over the peaks, summed, over the device time of
``prefill_attention`` inside ``_prefill_slot_jit``.  The admissions spanned and
the prefills traced differ by one at the edges, so the mean admission is
scaled to the prefill programs the trace holds.  The expansion of rows to
heads is an XLA product with no name of its own in a trace and is not in the
time nor in the count (``latent_rows_expanded`` has its rows).  A
configuration without latent attention, or a trace without the kernel, gives
nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work, work_latent, work_moe


def read(trace, facts, cell, peak, **_):
    c = cell.config
    if not work_latent.applies(c):
        return None
    admits = [s for s in work_moe.traced_spans("engine.admit", facts,
                                               "latent_prefill_form")
              if s.attrs.get("prefill_attention") == "tiled"]
    dev = tr.fullest(trace)
    secs, calls = work_moe.kernel_seconds_in(dev, "prefill_attention",
                                             "_prefill_slot_jit")
    _, runs = tr.module_seconds(dev, "_prefill_slot_jit")
    if not admits or not calls or not runs or secs <= 0:
        return None
    least = sum(work.least_seconds(work_latent.prefill_work(c, s.attrs), peak,
                                   ops_key="ops")
                for s in admits) / len(admits)
    return 100.0 * least * runs / secs
