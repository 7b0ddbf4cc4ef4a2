"""End-to-end arithmetic over the window's ``GenerationResult``s.

Every metric is taken over all requests of all calls in the window:
rates over the whole window, tails over every request, time per output
token per request.
"""
from __future__ import annotations

from typing import Iterable, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated ``q``-th percentile (numpy's default)."""
    if not len(values):
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def requests(results: Iterable) -> List[dict]:
    return [r for res in results for r in res.per_request]


def output_tok_s(results: Sequence, window_s: float) -> float:
    """Tokens generated in the window's calls, over the window."""
    return sum(r["new_tokens"] for r in requests(results)) / window_s


def ttft_p90_s(results: Sequence) -> float:
    """From the start of each request's ``generate`` call to its first
    token, slot wait included; 90th percentile over all requests."""
    return percentile([r["ttft_s"] for r in requests(results)], 90)


def tpot_p90_ms(results: Sequence) -> float:
    """Per request ``(latency - ttft) / (new_tokens - 1)``; 90th
    percentile over all requests that decoded at least once."""
    per = [1e3 * (r["latency_s"] - r["ttft_s"]) / (r["new_tokens"] - 1)
           for r in requests(results) if r["new_tokens"] > 1]
    return percentile(per, 90)


def decode_batch_mean(results: Sequence) -> float:
    """Decode tokens per batched decode dispatch: each request's first
    token comes from its prefill, every other from a decode step."""
    reqs = requests(results)
    steps = sum(res.steps for res in results)
    return (sum(r["new_tokens"] for r in reqs) - len(reqs)) / steps
