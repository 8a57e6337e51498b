"""Percent of the window the host spent inside DFSClient.read / .write:
the authenticated, MAC'd packet plane.  One reader for every
``packet_plane_share.<end-to-end metric>`` split."""
from chipbench.reduce import span_share


def read(run):
    return span_share(run.spans, "packet", run.window)
