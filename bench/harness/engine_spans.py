"""The serve engine's own host spans in a reduced trace.

The engine writes one ``jax.profiler.TraceAnnotation`` per admission,
prefill chunk, and decode step's dispatch, read-back and acceptance
(``repro.serve.spans``); ``Trace.host`` keeps each as ``[name, start_ns,
duration_ns]``.  The names are copied here, not imported, so that a
reader also runs against a program that writes none of them, and then
finds nothing.
"""
from __future__ import annotations

from typing import List, Tuple

ADMIT = "serve.admit"
PREFILL = "serve.prefill"
DISPATCH = "serve.dispatch"
READBACK = "serve.readback"


def intervals(trace, name: str) -> List[Tuple[int, int]]:
    """``(start_ns, end_ns)`` of every host span named ``name``, in
    order of start."""
    return sorted((s, s + d) for n, s, d in trace.host if n == name)
