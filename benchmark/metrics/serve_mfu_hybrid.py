"""``serve_mfu`` for a decoder whose layers are of two kinds: the whole
serving step's share of the chip's bf16 peak, by ``serve_mfu``'s own reader
and token count with one number changed: 2 x matrix parameters per token
counts every layer as its kind in ``layer_types``
(``work_gdn.hybrid_token_flops``) where ``work.decoder_token_flops`` counts a
Llama layer.  Attention products and the recurrence are left out, so it
under-counts.  A configuration without ``layer_types`` gives nothing to
read."""
import types

from benchmark import work_gdn


def read(facts, cell, peak, work, **_):
    if "layer_types" not in cell.config:
        return None
    counts = types.SimpleNamespace(
        decoder_token_flops=lambda *_: work_gdn.hybrid_token_flops(cell.config),
        lm_head_flops=work.lm_head_flops)
    return cell.reader("serve_mfu").read(facts=facts, cell=cell, peak=peak,
                                         work=counts)
