"""The paged decode kernel's share of its roofline: the least time the
chip could take for the kernel's algorithmic work (the larger of bytes
over peak HBM bandwidth and operations over peak bf16 rate; the bytes are
K and V of each decode token's valid context plus q and out, all layers,
counted from the traced requests' lengths by the family's
``paged_kernel_work``) over the device time of the kernel's operations
inside the ``jit_decode_step_multi`` program, on chip 0, their intervals
merged.  Under a mesh chip 0's kernel streams only its own shard's heads,
so the work counted is that shard's.  The kernel's Mosaic custom call
takes the name of its jitted wrapper, ``_paged_flash_decode.<n>`` (the
compiled program's text for a v5e); the program's other custom calls
(``ConcatBitcast``, ``AllocateBuffer``) are not the kernel.  Layer:
kernels (``kernels/paged_attention.py``)."""
import re

PROGRAM = "decode_step_multi"
KERNEL = re.compile(r"_paged_flash_decode")


def read(run):
    ns = run.trace.ops_ns(0, lambda n: bool(KERNEL.search(n)),
                          lambda m: PROGRAM in m)
    if not ns:
        return None
    nbytes = flops = 0
    for p, n in run.served:
        b, f = run.family.paged_kernel_work(run.dims, p, n,
                                            run.model_shards)
        nbytes, flops = nbytes + b, flops + f
    least = max(nbytes / run.peaks["hbm_bytes_s"],
                flops / run.peaks["bf16_flops_s"])
    return 100.0 * least / (ns / 1e9)
