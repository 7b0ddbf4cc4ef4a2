"""Time from the start of a request's ``generate`` call to its first
admission to a slot: the engine's ``admit_s`` stamp, 90th percentile over
the traced requests.  What ``ttft_p90_s`` holds beyond it is the
request's own prefill.  Layer: scheduler (``serve/scheduler.py``,
admission in ``serve/engine.py``)."""
from bench.harness import stats


def read(run):
    reqs = stats.requests(run.results)
    if not reqs or any("admit_s" not in r for r in reqs):
        return None
    return stats.percentile([r["admit_s"] for r in reqs], 90)
