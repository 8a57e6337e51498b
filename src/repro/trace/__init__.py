"""``repro.trace`` — sampled request tracing, unified counters, exporters.

The observability layer for the timed plane, and with :mod:`.wall` for
the served data plane:

* :class:`Tracer` / :class:`Span` — head-sampled, zero-cost-when-off
  span recording (install via ``env.sim.tracer`` or
  ``Scenario.run(tracer=...)``)
* :class:`CounterRegistry` / :func:`registry_for` — one snapshot-diffable
  namespace over the sim's scattered counters
* :func:`to_chrome_trace` / :func:`write_chrome_trace` — Perfetto /
  ``chrome://tracing`` export
* :mod:`repro.trace.attr` — per-request / per-policy latency attribution
  into wire / hpu_queue / hpu_exec / pcie / host_cpu / client buckets
* :mod:`repro.trace.wall` — wall-clock spans of the served data plane
  (``span(name, layer)``, on while a ``Tracer.wall()`` is installed),
  :func:`dataplane_registry` over its always-on counters and
  :func:`namenode_registry` with the NameNode's
"""

from .tracer import BUCKETS, Span, Tracer
from .counters import (CounterRegistry, dataplane_registry,
                       namenode_registry, registry_for)
from .perfetto import to_chrome_trace, write_chrome_trace
from . import attr, wall

__all__ = [
    "BUCKETS", "Span", "Tracer",
    "CounterRegistry", "dataplane_registry", "namenode_registry",
    "registry_for",
    "to_chrome_trace", "write_chrome_trace",
    "attr", "wall",
]
