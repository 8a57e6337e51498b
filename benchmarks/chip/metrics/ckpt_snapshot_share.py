"""Percent of each save spent taking its snapshot: the program's
``ckpt.snapshot`` spans (device arrays to host memory) over its
``ckpt.save`` spans, from ``save()``'s entry to the manifest, in the
window."""
from chipbench.reduce import measure, program_union


def read(run):
    if not run.program_spans:
        return None
    save = measure(program_union(run.program_spans, ("ckpt.save",),
                                 run.window))
    if not save:
        return None
    snapshot = measure(program_union(run.program_spans, ("ckpt.snapshot",),
                                     run.window))
    return 100 * snapshot / save
