"""How full the grouped expert product's tiles are in decode: the (token,
expert) pairs computed over the rows of the tiles that computed them
(``expert_pairs_held`` over ``expert_tile_rows`` of the traced
``engine.step`` spans, both summed over the expert layers), %.  A tile
belongs to one expert, so at a pair or two an expert most of its rows are
padding that the MXU computes and nobody reads.  A program whose spans carry
no tile count gives nothing to read."""
from benchmark import work_moe


def read(facts, **_):
    steps = work_moe.traced_spans("engine.step", facts, "expert_tile_rows")
    rows = sum(s.attrs["expert_tile_rows"] for s in steps)
    if not rows:
        return None
    return 100.0 * sum(s.attrs["expert_pairs_held"] for s in steps) / rows
