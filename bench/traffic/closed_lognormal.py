"""Closed-loop batches with lognormal prompt and output lengths.

Each ``generate`` call takes ``batch`` requests, and calls run back to
back.  The lengths are the same multiset in every call: ``batch``
stratified quantiles of each lognormal (the ``(i + 0.5) / batch``
quantiles), clipped, prompts rounded up to ``prompt.grid`` tokens,
paired by one fixed shuffle.  Call ``k`` serves them in the ``k``-th of a
fixed sequence of orders, the same for every seed: in a closed batch the
order decides which requests wait for a slot, so an order drawn from the
seed would change the schedule, and with it the tails, from seed to
seed.  The seed draws the token ids, uniform over ``[1, vocab)``.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np

# pair the prompt quantiles with the output quantiles, and order each
# call; fixed, so that every seed serves the same calls
_PAIRING_SEED = 20240229
_ORDER_SEED = 20240301


def _quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    grid = int(spec.get("grid", 1))
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        v = min(max(v, spec["min"]), spec["max"])
        out.append(int(math.ceil(v / grid) * grid))
    return out


class Mix:
    def __init__(self, params: Dict[str, Any], vocab: int, seed: int):
        self.batch = int(params["batch"])
        self.vocab = int(vocab)
        self.seed = int(seed) % 2 ** 64
        prompts = _quantiles(params["prompt"], self.batch)
        outputs = _quantiles(params["output"], self.batch)
        perm = np.random.default_rng(_PAIRING_SEED).permutation(self.batch)
        self.shapes: List[Tuple[int, int]] = [
            (prompts[i], outputs[j]) for i, j in enumerate(perm)]

    def call(self, k: int) -> Tuple[List[List[int]], List[int]]:
        """Prompts (token ids) and max new tokens of the ``k``-th call."""
        order = np.random.default_rng([_ORDER_SEED, int(k)]).permutation(
            self.batch)
        shapes = [self.shapes[i] for i in order]
        rng = np.random.default_rng([self.seed, int(k)])
        ids = rng.integers(1, self.vocab, size=sum(p for p, _ in shapes))
        prompts, at = [], 0
        for p, _ in shapes:
            prompts.append(ids[at:at + p].tolist())
            at += p
        return prompts, [m for _, m in shapes]


def make(params: Dict[str, Any], vocab: int, seed: int) -> Mix:
    return Mix(params, vocab, seed)
