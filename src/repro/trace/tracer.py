"""Sampled, zero-cost-when-off request tracing for the timed plane.

The simulator already *knows* every interval the trace needs — a
:class:`~repro.sim.engine.SerialResource` returns ``(start, end)`` the
moment a service is accepted, the PsPIN model threads ``t0`` /
``t_compute_done`` through its handler steps, and the network computes
arrival times analytically.  The tracer therefore never schedules
events: instrumentation *records* intervals the model computed anyway,
so enabling it cannot perturb the simulated timeline (the anchor suite
asserts bit-exactness, see ``tests/test_trace.py``).

Cost model:

* **off** (``sim.tracer is None``, the default) — every hook is a single
  attribute load + ``is None`` branch; no tuple, no span, no call.
* **sampled out** — head-based sampling by request id
  (``rid % sample_every == 0``); unsampled requests take one modulo and
  allocate nothing.
* **sampled in** — one :class:`Span` per interval, appended to a bounded
  buffer (``max_spans``); past the bound spans are counted in
  ``dropped`` instead of growing memory.

Span attributes follow the contract
``{request, policy, stage, node, resource}``: ``rid`` / ``pid`` name the
request and policy instance (``register_policy`` maps pids to the
human-readable policy names the registry/telemetry use), ``name`` is the
stage, ``resource`` the track the span occupies (e.g. ``n3.egress``),
and ``cat`` the attribution bucket (see :mod:`repro.trace.attr`).

A tracer made with ``clock="wall"`` records the served data plane
instead: spans timed on ``time.perf_counter_ns`` by :mod:`repro.trace.wall`
while the tracer is installed there, ``cat`` naming the layer,
``resource`` the thread and ``parent`` the enclosing span.
"""

from __future__ import annotations

import itertools

#: attribution buckets every span category must fall into (or "request"
#: for root spans, which attribution skips)
BUCKETS = ("wire", "hpu_queue", "hpu_exec", "pcie", "host_cpu", "client")


class Span:
    """One closed interval on one resource track (micro-struct; traces
    hold millions of these, hence ``__slots__`` and no dataclass)."""

    __slots__ = ("name", "cat", "t0", "t1", "rid", "pid", "node", "resource",
                 "args", "parent")

    def __init__(self, name, cat, t0, t1, rid=None, pid=None, node=None,
                 resource=None, args=None, parent=None):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = t1
        self.rid = rid
        self.pid = pid
        self.node = node
        self.resource = resource
        self.args = args
        #: the enclosing :class:`Span` (wall-clock spans only)
        self.parent = parent

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, {self.cat!r}, [{self.t0:.0f}, {self.t1:.0f}) "
                f"rid={self.rid} res={self.resource})")


class Tracer:
    """Head-based sampling tracer with a bounded span buffer.

    Install with ``env.sim.tracer = Tracer(sample_every=64)`` (or pass
    ``tracer=`` to :meth:`repro.sim.workload.Scenario.run`).  Sampling is
    decided once per request from its id, so every span of a sampled
    request is kept and unsampled requests leave no trace at all.
    """

    def __init__(self, sample_every: int = 64, max_spans: int = 1_000_000,
                 clock: str = "sim"):
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, got {sample_every}")
        if clock not in ("sim", "wall"):
            raise ValueError(f"clock must be 'sim' or 'wall', got {clock!r}")
        if clock == "wall" and sample_every != 1:
            raise ValueError("a wall-clock tracer keeps every span "
                             "(sample_every=1)")
        self.sample_every = int(sample_every)
        self.max_spans = int(max_spans)
        #: "sim": simulated ns recorded by the timed plane; "wall":
        #: ``perf_counter_ns`` spans of the served data plane
        self.clock = clock
        self.spans: list[Span] = []
        self.dropped = 0
        self._policies: dict[int, str] = {}
        self._rids = itertools.count(1)

    @classmethod
    def wall(cls, max_spans: int = 1_000_000) -> "Tracer":
        """A tracer for :func:`repro.trace.wall.install`."""
        return cls(sample_every=1, max_spans=max_spans, clock="wall")

    def new_rid(self) -> int:
        """A fresh request id for a wall-clock root span."""
        return next(self._rids)

    def keep(self, span: Span) -> bool:
        """Append a closed span to the bounded buffer; False (counted in
        ``dropped``) past ``max_spans``."""
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
            return False
        self.spans.append(span)
        return True

    def sampled(self, rid) -> bool:
        """Head-based sampling decision for one request id."""
        if rid is None:
            return False
        return self.sample_every == 1 or rid % self.sample_every == 0

    def record(self, name, cat, t0, t1, rid=None, pid=None, node=None,
               resource=None, args=None):
        """Record one complete interval; returns the span (or None when
        the buffer bound was hit — counted in ``dropped``)."""
        sp = Span(name, cat, t0, t1, rid=rid, pid=pid, node=node,
                  resource=resource, args=args)
        return sp if self.keep(sp) else None

    def register_policy(self, pid: int, name: str) -> None:
        """Map a protocol instance id to its policy name (spans carry
        pids; exporters and attribution resolve them through this)."""
        self._policies[pid] = name

    def policy_name(self, pid) -> str:
        return self._policies.get(pid, f"pid{pid}" if pid is not None else "?")

    def clear(self) -> None:
        self.spans.clear()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self.spans)
