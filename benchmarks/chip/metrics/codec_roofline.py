"""Percent of its roofline the codec program reaches: the least time of
the window's GF matmuls (S * (k + n) * L bytes each, from the call's
shapes, over the HBM peak) over the device time of the whole jitted
codec program (pack, Pallas kernel and unpack)."""
from chipbench.reduce import CODEC_PROGRAM, in_window


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.program_s(CODEC_PROGRAM, run.window)
    calls = in_window(run.spans, "gf", run.window)
    if not kernel_s or not calls:
        return None
    least_s = sum(s.nbytes for s in calls) / run.peak("hbm_bytes_per_s")
    return 100 * least_s / kernel_s
