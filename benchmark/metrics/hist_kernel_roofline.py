"""The histogram kernels' share of their roofline: the least time for the
histogram passes alone (``work.hist_pass_work`` per level) over the device
time of the Pallas histogram kernels (custom calls named
``route_and_hist_pallas`` and ``build_hist_nodes_pallas``).  Bound by
memory."""
from benchmark import trace_reduce as tr

KERNELS = ["route_and_hist_pallas", "build_hist_nodes_pallas"]


def read(trace, facts, cell, peak, work, **_):
    n = facts.get("iterations")
    secs, calls = tr.op_seconds(tr.fullest(trace), KERNELS, "self_ns")
    if not n or not calls or secs <= 0:
        return None
    c = cell.config
    one = work.hist_pass_work(c["rows"], c["features"], c["max_bin"] + 1)
    least = work.tree_levels(c["num_leaves"]) * n * work.least_seconds(one, peak)
    return 100.0 * least / secs
