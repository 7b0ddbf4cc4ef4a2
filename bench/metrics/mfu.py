"""Model FLOPs of the traced calls' prompt and decode tokens over the
traced window, the chips and the chip's bf16 peak: the whole step's share
of the peak.  The counts are the algorithm's (``bench/harness/counts.py``:
causal attention, the LM head only where logits are needed), not what the
program executes.  Layer: device (whole step)."""
from bench.harness import counts


def read(run):
    flops = sum(counts.request_flops(run.dims, p, n) for p, n in run.served)
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["bf16_flops_s"])
