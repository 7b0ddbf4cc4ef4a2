"""Named host spans of the continuous serve loop.

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the same
profile as the device's programs, on the same clock, and costs under a
microsecond when no profiler runs, so the spans are always written.  The
keyword arguments carry the identifiers (``rid`` a request, ``slot``,
``step`` the decode step index) as the event's stats.

The names are stable: profile readers (the benchmark's per-layer
metrics) match them letter for letter.

=================  ===================================================
``serve.generate``  one ``generate`` call (``requests``)
``serve.admit``     one admission, from the pool's grant through the
                    slot's page-table write (``rid``, ``slot``,
                    ``covered``)
``serve.prefill``   one prefill chunk: upload, dispatch, and the wait for
                    it or the first token's read-back (``rid``, ``slot``,
                    ``tokens``, ``last``)
``serve.dispatch``  one decode step's uploads and the launches of the
                    decode program and its sampler (``step``, ``active``)
``serve.readback``  the blocking read of that step's sampled tokens
                    (``step``)
``serve.accept``    accepting those tokens and finishing requests
                    (``step``)
=================  ===================================================
"""
from __future__ import annotations

import jax

GENERATE = "serve.generate"
ADMIT = "serve.admit"
PREFILL = "serve.prefill"
DISPATCH = "serve.dispatch"
READBACK = "serve.readback"
ACCEPT = "serve.accept"


def span(name: str, **ids: int) -> jax.profiler.TraceAnnotation:
    """The host span ``name`` with its identifiers, as a context."""
    return jax.profiler.TraceAnnotation(name, **ids)
