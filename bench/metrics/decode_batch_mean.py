"""Decode tokens per batched decode dispatch over the traced calls, from
the engine's own counters (``GenerationResult.steps`` and tokens).  Layer:
engine (``serve/engine.py``, ``serve/scheduler.py``)."""
from bench.harness import stats


def read(run):
    if not any(res.steps for res in run.results):
        return None
    return stats.decode_batch_mean(run.results)
