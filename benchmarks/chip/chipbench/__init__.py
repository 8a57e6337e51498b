"""The chip benchmark of the storage data plane: one cell, one run.

``run.py`` next to this package is the entry point; this package holds
the yardstick that later changes to the program may not move: the
traffic generator (``cells``, with one file a kind of operation in
``kinds/``), the plain Reed-Solomon reference
(``reference``), the spans the benchmark records around the program's
layers (``spans``), the reduction from spans, the program's own spans
and device traces to numbers (``reduce``), the compile-cache policy (``cache``) and the
planted faults of the correctness control (``faults``).
"""
