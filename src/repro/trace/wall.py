"""Wall-clock spans of the served data plane, on the profiler's clock too.

The storage data plane (``StorageCluster``, ``DFSClient``, ``RSCode``,
``kernels/ops.py``, ``CheckpointManager``) opens a span around each
layer's work at shard granularity::

    with wall.span("dfs.write", "packet"):
        ...

Tracing is on only while a wall-clock :class:`~repro.trace.Tracer` is
installed (:func:`install` / :func:`uninstall`).

* **off** (nothing installed, the default) -- :func:`span` costs one
  global load and one ``is None`` test and returns the shared
  :data:`NULL` context; nothing is allocated.
* **on** -- each span becomes a :class:`~repro.trace.Span`: ``t0`` /
  ``t1`` from ``time.perf_counter_ns``, ``cat`` the layer, ``resource``
  the thread's name, ``parent`` the span enclosing it on the same thread
  (or the one passed in), and ``rid`` its parent's, or a fresh one for a
  root.  Closed spans go to the tracer's bounded buffer (``max_spans``,
  ``dropped``); a span left by an exception carries
  ``args={"failed": True}``.  Each span also opens a
  ``jax.profiler.TraceAnnotation`` of its name, so in a profiled run
  the program's spans lie in the ``.xplane.pb`` host plane on the same
  clock as the device's events.

A span that ends on another thread than it began on (a checkpoint save
starts on the caller's thread and finishes on its writer thread) is
opened with :func:`begin` and closed with :func:`end`.
"""

from __future__ import annotations

import threading
import time

from repro.trace.tracer import Span, Tracer

_tracer: Tracer | None = None
_local = threading.local()


class _Null:
    """The context :func:`span` returns while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL = _Null()


def install(tracer: Tracer) -> None:
    """Record the data plane's spans into ``tracer`` from now on."""
    global _tracer
    if tracer.clock != "wall":
        raise ValueError("install a wall-clock tracer (Tracer.wall())")
    if _tracer is not None and _tracer is not tracer:
        raise RuntimeError("another tracer is installed")
    _tracer = tracer


def uninstall() -> Tracer | None:
    """Stop recording; returns the tracer that was installed."""
    global _tracer
    tracer, _tracer = _tracer, None
    return tracer


def installed() -> Tracer | None:
    return _tracer


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Live:
    """An open span: its record, the tracer it goes to and its profiler
    annotation.  Opens when made; as a context manager it is also the
    innermost span of its thread until it exits."""

    __slots__ = ("tracer", "span", "note")

    def __init__(self, tracer: Tracer, name: str, layer: str,
                 parent: Span | None, t0: int | None = None):
        from jax.profiler import TraceAnnotation

        if parent is None:
            stack = _stack()
            parent = stack[-1].span if stack else None
        self.tracer = tracer
        self.note = TraceAnnotation(name)
        self.note.__enter__()
        self.span = Span(
            name, layer, time.perf_counter_ns() if t0 is None else t0, None,
            rid=parent.rid if parent is not None else tracer.new_rid(),
            resource=threading.current_thread().name, parent=parent)

    def close(self, failed: bool = False, t1: int | None = None) -> None:
        sp = self.span
        sp.t1 = time.perf_counter_ns() if t1 is None else t1
        self.note.__exit__(None, None, None)
        if failed:
            sp.args = {"failed": True}
        self.tracer.keep(sp)

    def __enter__(self) -> Span:
        _stack().append(self)
        return self.span

    def __exit__(self, etype, exc, tb):
        _stack().pop()
        self.close(failed=etype is not None)
        return False


def span(name: str, layer: str, parent: Span | None = None):
    """Context manager of one span of ``layer`` on this thread; ``parent``
    overrides the enclosing span (a worker thread's first span under a
    span another thread opened).  ``as`` binds the :class:`Span`, or None
    while tracing is off."""
    tracer = _tracer
    if tracer is None:
        return NULL
    return _Live(tracer, name, layer, parent)


def begin(name: str, layer: str, t0: int | None = None,
          parent: Span | None = None) -> _Live | None:
    """Open a span that :func:`end` may close on another thread; ``t0``
    (``perf_counter_ns``) when the caller took the time already, and
    ``parent`` as for :func:`span`.  None while tracing is off."""
    tracer = _tracer
    if tracer is None:
        return None
    return _Live(tracer, name, layer, parent, t0)


def end(live: _Live | None, failed: bool = False, t1: int | None = None
        ) -> None:
    """Close a span :func:`begin` opened (nothing for None)."""
    if live is not None:
        live.close(failed, t1)


def parent_of(live: _Live | None) -> Span | None:
    """The record of a :func:`begin` span, to pass as ``parent``."""
    return None if live is None else live.span
