"""The kernel-under-tune: an ACTS ``TunableSystem`` over Pallas tilings.

A ``KernelSUT`` scores one block configuration for one problem signature.
Two measurement modes:

* ``"time"``  — compile + wall-clock the kernel (the real thing; only
  meaningful on actual accelerator backends),
* ``"model"`` — the deterministic roofline cost model from
  ``repro.autotune.space`` (the CPU/interpret default: interpret-mode wall
  time measures the Python emulator, not the TPU).

Either way the metric is seconds (lower is better), so the unmodified ACTS
``Tuner`` — budget, duplicate-config cache, report — drives the search.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from repro.core.params import Config, ParameterSpace
from repro.core.tuner import PerfMetric

from .space import KERNELS, KernelSpace, shape_sig

__all__ = ["KernelSUT"]


class KernelSUT:
    def __init__(
        self,
        kernel: str,
        dims: Dict[str, int],
        dtype: str = "float32",
        mode: Optional[str] = None,  # None = time on TPU, model elsewhere
        interpret: Optional[bool] = None,
        timing_iters: int = 3,
        seed: int = 0,
    ):
        self.kspace = KernelSpace(kernel)
        self.kernel = kernel
        self.dims = self.kspace.validate_dims(dims)
        self.dtype = dtype
        self.timing_iters = timing_iters
        self.seed = seed
        self._interpret = interpret
        self._mode = mode
        self._inputs: Optional[tuple] = None
        self.name = f"kernel[{kernel}×{shape_sig(self.dims)}]"

    # lazy jax-touching properties so building a SUT never initializes jax
    @property
    def interpret(self) -> bool:
        if self._interpret is None:
            from repro.kernels.ops import default_interpret

            self._interpret = default_interpret()
        return self._interpret

    @property
    def mode(self) -> str:
        if self._mode is None:
            self._mode = "model" if self.interpret else "time"
        return self._mode

    def space(self) -> ParameterSpace:
        return self.kspace.space()

    @property
    def feasibility_model(self):
        """Static feasibility of a block config on this problem signature.

        Auto-detected by the ``Tuner``: statically-VMEM-infeasible tilings
        are pruned before they burn a test (in ``mode="time"`` they would
        compile-and-crash on real hardware; in ``mode="model"`` they would
        spend a budget unit to learn ``inf``).  Built on the same
        ``vmem_footprint`` the cost model evaluates, so pruning never
        disagrees with cost-model finiteness.
        """
        from repro.analysis.feasibility import kernel_feasibility

        return kernel_feasibility(self.kernel, self.dims, self.dtype)

    # ------------------------------------------------------------------
    def _get_inputs(self) -> tuple:
        if self._inputs is None:
            rng = np.random.default_rng(self.seed)
            self._inputs = self.kspace.definition.make_inputs(
                self.dims, self.dtype, rng)
        return self._inputs

    def test(self, config: Config) -> PerfMetric:
        d = self.kspace.definition
        if self.mode == "model":
            cost = float(d.model_cost(config, self.dims, self.dtype))
            return PerfMetric(value=cost, higher_is_better=False,
                              metrics={"mode": "model",
                                       "config": dict(config)})
        import jax

        inputs = self._get_inputs()
        # One compiled program per candidate.  Each kernel wrapper builds
        # its Pallas kernel afresh per call, so an eager call compiles the
        # kernel again every time, and its wall clock is compile time.
        run = jax.jit(lambda *xs: d.call(xs, config, self.interpret))
        jax.block_until_ready(run(*inputs))  # compile + first run
        best = float("inf")
        for _ in range(self.timing_iters):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*inputs))
            best = min(best, time.perf_counter() - t0)
        return PerfMetric(value=best, higher_is_better=False,
                          metrics={"mode": "time", "config": dict(config)})

    def test_batch(self, configs) -> list:
        """One evaluator call per candidate round (BatchEvaluator protocol).

        The cost model is scalar math, so the batch is a plain loop —
        value-identical to per-config ``test`` — but a composite/batched
        tuner still dispatches the whole round in a single call.
        """
        return [self.test(c) for c in configs]
