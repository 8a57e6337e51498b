"""Percent of the codec programs' device time spent in their Pallas
kernel, the one Mosaic call (``tpu_custom_call``) inside each jitted
``_encode_planes_batched`` program; the rest is the bit-plane pack and
unpack around it."""
from chipbench.reduce import CODEC_PROGRAM, intersect, measure, union

#: a device op that is the Pallas kernel (in the HLO text of its name)
KERNEL_OP = 'custom_call_target="tpu_custom_call"'


def read(run):
    if run.trace is None:
        return None
    kernel = program = 0
    for dev, mods in run.trace.modules.items():
        progs = intersect(union((s, e) for n, s, e in mods
                                if CODEC_PROGRAM in n), run.window)
        calls = union((s, e) for n, s, e in run.trace.ops.get(dev, ())
                      if KERNEL_OP in n)
        program += measure(progs)
        kernel += measure(intersect(calls, progs))
    if not program:
        return None
    return 100 * kernel / program
