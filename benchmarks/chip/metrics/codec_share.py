"""Percent of the window the host spent inside RSCode.encode_stripes /
decode_stripes, host-device copies and device time included."""
from chipbench.reduce import span_share


def read(run):
    return span_share(run.spans, "codec", run.window)
