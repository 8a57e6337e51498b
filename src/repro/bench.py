"""Shared benchmark artifact writer + claims gate.

Every ``BENCH_*.json`` artifact has the same shape::

    {"bench": <suite>, "metric": <units of the row columns>,
     "config": {...}, "claims": {...},
     "rows": [{"name", "us_per_call", "derived"}, ...]}

Historically each suite hand-rolled this dump (and the anchor gate in
``tools/check_anchors.py`` re-implemented the claim lookups); the
helpers here are the one implementation the per-suite ``write_artifact``
shims, ``benchmarks.run``, and the anchor gate all delegate to.  Lives
in ``repro`` (not ``benchmarks/``) so ``repro.control.sweep`` can reach
it without a path dance.
"""

from __future__ import annotations

import json
import os
import re
import sys

#: The root of the checkout this package is imported from.
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: JAX's persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed directory at the root of the checkout (a temporary or
#: per-run directory would never be found again).
COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; call before the first
    compile, never at import.  A ``JAX_COMPILATION_CACHE_DIR`` set from
    outside wins (JAX reads it itself and no other directory is set);
    otherwise the cache lives in :data:`COMPILE_CACHE_DIR`.  Compiles of
    0.1 s and up are kept: most kernel compiles take under the default
    1 s threshold.  A Pallas kernel carries its source file's path into
    the program and so into the cache key: the checkout's root is cut
    from source paths, so that every checkout shares the entries.
    Returns the directory in use."""
    import jax

    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(_CHECKOUT + os.sep))

    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return where


def write_bench_artifact(
    out: str,
    bench: str,
    rows: list[tuple],
    metric: str | None = None,
    claims: dict | None = None,
    config: dict | None = None,
    extra: dict | None = None,
) -> None:
    """Write one ``BENCH_*.json`` artifact in the common schema.

    ``rows`` are ``(name, us_per_call, derived)`` triples; ``claims``
    and ``config`` are included only when given (older artifacts omit
    them); ``extra`` merges additional top-level keys (e.g. a manifest's
    ``artifacts`` map)."""
    doc: dict = {"bench": bench}
    if metric is not None:
        doc["metric"] = metric
    if config is not None:
        doc["config"] = config
    if claims is not None:
        doc["claims"] = claims
    if extra:
        doc.update(extra)
    doc["rows"] = [
        {"name": n, "us_per_call": u, "derived": d} for n, u, d in rows
    ]
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)


def gate_claims(path_or_doc, gates: list[tuple]) -> list[str]:
    """Check recorded claims against bounds; returns readable errors.

    ``gates`` entries are ``(claim_key, op, bound, message)`` where op is
    one of ``">="``, ``"<="``; a missing claim is itself an error.  Used
    by ``tools/check_anchors.py`` so each new suite doesn't re-implement
    the lookup/compare/format dance."""
    if isinstance(path_or_doc, dict):
        doc = path_or_doc
    else:
        try:
            with open(path_or_doc) as f:
                doc = json.load(f)
        except OSError:
            return [f"  missing artifact {path_or_doc}"]
    claims = doc.get("claims", {})
    errors = []
    for key, op, bound, message in gates:
        val = claims.get(key)
        if val is None:
            errors.append(f"  claim {key} missing")
            continue
        ok = val >= bound if op == ">=" else val <= bound
        if not ok:
            errors.append(
                f"  {message} ({key} = {val:.3g}, wanted {op} {bound:.3g})"
            )
    return errors
