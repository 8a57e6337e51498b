"""Host spans around calls into the program's layers, from the
benchmark's side, read by the metrics that predate the program's own
spans (which a traced run hands the readers as ``Run.program_spans``).

Only a ``--trace 1`` run installs the wrappers; :func:`install` returns
the function that takes them out again.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import time

#: (module, class or None, attribute, category).  Categories are the
#: layers of PERF.md: the packet plane (authenticated shard reads and
#: writes), the coding layer (batched encode / decode), one GF matmul
#: dispatch (whose shapes give the codec's least bytes), the storage
#: cluster's entry points and the checkpoint save.
TARGETS = (
    ("repro.core.handlers", "DFSClient", "write", "packet"),
    ("repro.core.handlers", "DFSClient", "read", "packet"),
    ("repro.core.erasure", "RSCode", "encode_stripes", "codec"),
    ("repro.core.erasure", "RSCode", "decode_stripes", "codec"),
    ("repro.kernels.ops", None, "gf_matmul_bytes_batched", "gf"),
    ("repro.checkpoint.storage", "StorageCluster", "write_object_bulk",
     "cluster"),
    ("repro.checkpoint.storage", "StorageCluster", "read_objects", "cluster"),
    ("repro.checkpoint.storage", "StorageCluster", "repair_node", "cluster"),
    ("repro.checkpoint.manager", "CheckpointManager", "save", "save"),
)


@dataclasses.dataclass(frozen=True)
class Span:
    category: str
    name: str
    start: int
    end: int
    #: least bytes of a GF matmul dispatch (``gf`` spans), else 0
    nbytes: int = 0


class SpanStore:
    """Spans kept in memory; ``enabled`` is cleared while the window's
    clock is stopped."""

    def __init__(self):
        self.spans: list[Span] = []
        self.enabled = True


def _gf_bytes(args) -> int:
    from chipbench.reduce import gf_matmul_bytes

    coeffs, data = args[0], args[1]
    n, k = coeffs.shape
    s, _, length = data.shape
    return gf_matmul_bytes(n, k, s, length)


def _wrap(fn, store: SpanStore, category: str, name: str):
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        t0 = time.perf_counter_ns()
        try:
            return fn(*args, **kwargs)
        finally:
            if store.enabled:
                nbytes = _gf_bytes(args) if category == "gf" else 0
                store.spans.append(Span(category, name, t0,
                                        time.perf_counter_ns(), nbytes))

    return wrapper


def install(store: SpanStore):
    """Wrap every target; returns the function that unwraps them."""
    undo = []
    for module, cls, attr, category in TARGETS:
        owner = importlib.import_module(module)
        if cls is not None:
            owner = getattr(owner, cls)
        orig = owner.__dict__[attr]
        name = f"{cls}.{attr}" if cls else f"{module.rsplit('.', 1)[1]}.{attr}"
        setattr(owner, attr, _wrap(orig, store, category, name))
        undo.append((owner, attr, orig))

    def uninstall():
        for owner, attr, orig in reversed(undo):
            setattr(owner, attr, orig)

    return uninstall
