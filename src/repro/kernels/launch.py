"""Shared launch-knob plumbing for the Pallas kernels.

Every kernel space carries two launch knobs (``repro.autotune.space``):

* ``dim_semantics`` — "parallel" marks the embarrassingly-parallel outer
  grid dims for Mosaic (``CompilerParams.dimension_semantics``), which
  lets the two TPU cores split them (megacore); dims that carry VMEM
  scratch across steps (online-softmax/kv, GLA state) stay "arbitrary".
* ``num_warps`` — the GPU-lowering occupancy hint.  Mosaic has no analog,
  so on TPU it is a modelled knob only (the roofline ``_dispatch_s``
  term); kernels accept it for signature parity with a Triton lowering.

``launch_params`` builds the compiler params (or ``None``) so each kernel
declares just its grid shape and how many trailing dims are sequential.
"""
from __future__ import annotations

from typing import Optional

from jax.experimental.pallas import tpu as pltpu

__all__ = ["launch_params"]


def launch_params(dimension_semantics: Optional[str], n_grid_dims: int,
                  n_sequential: int, interpret: bool):
    """``pltpu.CompilerParams`` for the launch knobs, or ``None``.

    ``n_sequential`` trailing grid dims are always "arbitrary" (they carry
    scratch state); the leading dims become "parallel" when requested.
    Interpret mode takes no compiler params.
    """
    if interpret or dimension_semantics != "parallel":
        return None
    sem = (("parallel",) * (n_grid_dims - n_sequential)
           + ("arbitrary",) * n_sequential)
    return pltpu.CompilerParams(dimension_semantics=sem)
