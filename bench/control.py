#!/usr/bin/env python3
"""Readings that set a cell's limit, in one process on the chip.

    python bench/control.py --workload <cell> --seeds 1-12 --control-seeds 1-3

For each seed: the program's reading, as a run takes it (weights and
traffic from the seed, one call of the mix at the cell's own load, the
served tokens of the run's sample against the float32 reference).  For
each control seed also the control's reading: on the same prompts and
tokens, the token that the reference computed with fp8 matmul inputs
puts first, measured by the float32 reference.  Both go through the
run's own comparison (``runner.judge``), so each row also says whether
the run would be ``correct``.  The benchmark's own runs never run this.  Prints one JSON line per seed and writes them all to
``chiprun_out/control/<cell>.json``.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from bench.harness import runner, spec
    from bench.harness.program import Program

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing was run", file=sys.stderr)
        return 1
    runner.enable_compile_cache()
    gen = spec.traffic_generator(cell.traffic)
    rows = []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t = time.time()
        prog = Program(cell.config_name, cell.config, seed, cell.bench_dir)
        mix = gen.make(cell.traffic, prog.dims.vocab, seed)
        prog.start()
        prog.generate(*prog.warm_prompts(mix.shapes))
        calls, results, _ = runner._window(prog, mix, 0.0)
        prog.stop()
        sample = runner.check_sample(calls, results,
                                     cell.traffic["check_requests"], seed)
        failed = sum(len(t) != m for (_, max_new), res in zip(calls, results)
                     for t, m in zip(res.tokens, max_new))
        row = {"seed": seed}
        for key, want, precision in (("program", args.seeds, "float32"),
                                     ("control_fp8", args.control_seeds,
                                      "fp8")):
            if seed in want:
                got, compared, correct = runner.judge(
                    prog.weights, cell.config, sample, failed, precision,
                    cell.bench_dir)
                row[key] = dict(got, compared=compared, correct=correct)
        row["seconds"] = time.time() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
        del prog, calls, results, sample
        gc.collect()
    out = os.path.join(ROOT, "chiprun_out", "control")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.workload + ".json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
