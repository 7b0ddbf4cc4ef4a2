"""Collective operations' device time (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all; their intervals merged)
over the device's busy time, on the busiest chip.  Layer: mesh
(``dist/sharding.py``; TP decode under ``shard_map``)."""


def read(run):
    tr = run.trace
    if tr.n_devices < 2:
        return None
    dev = max(range(tr.n_devices), key=tr.busy_ns)
    coll = tr.collective_ns(dev)
    if not coll:
        return None
    return 100.0 * coll / tr.busy_ns(dev)
