"""Model FLOPs of the traced calls' prompt and decode tokens over the
traced window, the chips and the chip's bf16 peak: the whole step's share
of the peak.  The counts are the algorithm's (the family's
``request_flops``, ``bench/families/<family>.py``: for the whole model,
whatever the mesh), not what the program executes.  Layer: device (whole
step)."""


def read(run):
    flops = sum(run.family.request_flops(run.dims, p, n)
                for p, n in run.served)
    return 100.0 * flops / (run.trace.window_s * run.chips
                            * run.peaks["bf16_flops_s"])
