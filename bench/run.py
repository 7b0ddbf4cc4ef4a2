#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The run draws the weights and the
traffic from ``--seed``, warms up every program shape the mix uses, then
serves closed batches of the mix back to back for ``--seconds`` through
the program's paged serve engine.  With ``--trace 1`` it serves one call
under the profiler instead and reports the per-layer metrics.  Then it
compares a sample of the served tokens with the plain float32 reference
(``bench/reference``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number compared with its
limit); the numbers compared are also the last lines of standard error.
It exits non-zero, with no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program (``src/``) is missing.
"""
import time

_T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is missing ({src}); nothing was run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    sys.path.insert(0, src)
    from bench.harness import runner, spec

    cell = spec.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s); "
              "nothing was run", file=sys.stderr)
        return 1
    out = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          _T0, devices[:cell.chips])
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
