"""The host's turn between two decode steps: from the end of step k's
``serve.readback`` span (its tokens in hand) to the end of step k+1's
``serve.dispatch`` span (the next step launched), mean over the pairs of
consecutive steps with no ``serve.prefill`` or ``serve.admit`` span
between them.  Layer: engine host loop (``serve/engine.py``)."""
import bisect

from bench.harness import engine_spans as es


def read(run):
    tr = run.trace
    dispatch = es.intervals(tr, es.DISPATCH)
    readback = es.intervals(tr, es.READBACK)
    if len(dispatch) < 2 or len(readback) != len(dispatch):
        return None
    other = sorted(s for s, _ in es.intervals(tr, es.PREFILL)
                   + es.intervals(tr, es.ADMIT))
    turns = []
    for (_, got), (launch, launched) in zip(readback, dispatch[1:]):
        if bisect.bisect_left(other, got) == bisect.bisect_left(other,
                                                               launch):
            turns.append(launched - got)
    if not turns:
        return None
    return sum(turns) / len(turns) / 1e6
