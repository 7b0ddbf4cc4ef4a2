#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reads.

    python bench/tests/data/record_v5e_trace.py --workload <cell> --out <dir>

On one TPU chip: the cell's model at its widths serves three short
requests (prompts of 576, 128 and 64 tokens, so two chunks of the first,
6 new tokens each) once to compile, then once more under the profiler
exactly as a traced run records it.  Writes ``<dir>/trace.xplane.pb`` and
``<dir>/summary.json`` (each device plane's lines and the most frequent
event names, and every per-layer metric of the cell read from it).
"""
import argparse
import collections
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
PROMPTS = (576, 128, 64)
NEW = 6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import glob

    import jax
    from jax.profiler import ProfileData

    from bench.harness import runner, spec
    from bench.harness.peaks import peaks_for
    from bench.harness.program import Program

    if jax.devices()[0].platform != "tpu":
        print("record: no TPU; nothing was run", file=sys.stderr)
        return 1
    runner.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    prog = Program(cell.config_name, cell.config, 1)
    prog.start()

    class Mix:
        shapes = [(p, NEW) for p in PROMPTS]

        def call(self, k):
            return ([[1 + (i + t) % 97 for t in range(p)]
                     for i, p in enumerate(PROMPTS)], [NEW] * len(PROMPTS))

    prog.generate(*Mix().call(0))
    tdir = os.path.join(args.out, "raw")
    shutil.rmtree(tdir, ignore_errors=True)
    calls, results, window_s, tr = runner._traced_window(prog, Mix(), tdir)
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    dest = os.path.join(args.out, "trace.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(tdir)
    path = dest
    summary = {"window_s": window_s, "bytes": os.path.getsize(path),
               "planes": {}}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = collections.Counter(ev.name for ev in line.events)
            lines[line.name] = names.most_common(12)
        summary["planes"][plane.name] = lines
    run = runner.Run(cell=cell, dims=prog.dims, chips=1,
                     peaks=peaks_for(jax.devices()[0].device_kind),
                     results=results, trace=tr, family=prog.family,
                     model_shards=prog.model_shards)
    summary["metrics"] = {m["name"]: spec.metric_reader(m["name"])(run)
                          for m in cell.per_layer}
    summary["breakdown"] = tr.breakdown()
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary["metrics"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
