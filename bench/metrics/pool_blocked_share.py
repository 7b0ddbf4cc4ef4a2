"""Share of decode steps before which admission stopped with a free slot
and a pending request because the KV pool could not hold the head's
reservation: the engine's ``admission_blocked`` over its ``steps``, over
the traced calls.  Layer: paging (``serve/paging.py``)."""


def read(run):
    if not all(hasattr(res, "admission_blocked") for res in run.results):
        return None
    steps = sum(res.steps for res in run.results)
    if not steps:
        return None
    return 100.0 * sum(res.admission_blocked for res in run.results) / steps
