"""How much of a decode step latent attention's kernel is: the device time
of ``latent_decode_attention`` inside ``_decode_step_jit`` over the
device-busy time inside that program, fullest device.  A program without the
kernel gives nothing to read."""
from benchmark import trace_reduce as tr
from benchmark import work_moe


def read(trace, **_):
    dev = tr.fullest(trace)
    secs, calls = work_moe.kernel_seconds_in(dev, "latent_decode_attention",
                                             "_decode_step_jit")
    step_s, runs = tr.module_seconds(dev, "_decode_step_jit")
    if not calls or not runs or step_s <= 0:
        return None
    return 100.0 * secs / step_s
