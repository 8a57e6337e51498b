"""Percent of the window the host spent in the NameNode's own work: inside
the program's ``nn.*`` spans (create, add_block, commit, read) and
outside the ``cluster.write`` spans under them (the encode and the
internal blocks' writes)."""
from chipbench.reduce import intersect, measure, program_union

SPANS = ("nn.create", "nn.add_block", "nn.commit", "nn.read")


def read(run):
    if not run.program_spans:
        return None
    nn = program_union(run.program_spans, SPANS, run.window)
    if not nn:
        return None
    write = program_union(run.program_spans, ("cluster.write",), run.window)
    own = measure(nn) - measure(intersect(nn, write))
    return 100 * own / measure(run.window)
