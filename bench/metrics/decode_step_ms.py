"""Device time of one decode step: the ``jit_decode_step_multi`` program's
device time over its launches, on chip 0.  Layer: model decode
(``Model.decode_step_multi``).  Under a mesh the program keeps its name
(each chip runs its shard of the same program), so the reader reads chip
0's launches there too."""
PROGRAM = "decode_step_multi"


def read(run):
    mods = run.trace.modules(0, lambda n: PROGRAM in n)
    if not mods:
        return None
    return sum(m[2] for m in mods) / len(mods) / 1e6
