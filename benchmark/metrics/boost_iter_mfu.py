"""The whole iteration's share of the chip: the least time the chip could
take for one iteration's required work (``work.boost_iteration_work``: per
level one read of every row's bins and gradient pair and one add per
feature, plus the gradient and margin passes; the larger of bytes over the
HBM peak and operations over the arithmetic peak) over the measured time per
iteration."""


def read(trace, facts, cell, peak, work, **_):
    n = facts.get("iterations")
    if not n:
        return None
    c = cell.config
    least = work.least_seconds(work.boost_iteration_work(
        c["rows"], c["features"], c["max_bin"] + 1, c["num_leaves"]), peak)
    return 100.0 * least * n / trace["window_s"]
