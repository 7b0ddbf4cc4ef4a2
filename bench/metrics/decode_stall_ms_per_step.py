"""Host time that prefill takes between decode steps, per decode step:
the summed ``serve.prefill`` spans that lie between the first and the
last decode step's ``serve.dispatch`` span, over the number of decode
steps.  Every slot that is decoding waits out those chunks.  Layer:
engine (prefill against decode scheduling, ``serve/engine.py``)."""
from bench.harness import engine_spans as es


def read(run):
    steps = es.intervals(run.trace, es.DISPATCH)
    if not steps:
        return None
    first, last = steps[0][0], steps[-1][0]
    stall = sum(e - s for s, e in es.intervals(run.trace, es.PREFILL)
                if first < s and e <= last)
    return stall / 1e6 / len(steps)
