import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any jax import (jax locks the platform
# and device count at first init).  The dry-run is a CPU analysis of a
# 512-device mesh: it never takes an accelerator, even on a host that has
# one.  This module is the ONLY place the 512 placeholder devices exist;
# smoke tests and benches see 1 device.
"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape × mesh) cell:
  * build the production mesh (16×16 single pod / 2×16×16 multi-pod),
  * construct abstract params / optimizer state / batch / KV-cache
    (ShapeDtypeStruct stand-ins — no allocation),
  * ``jax.jit(step, in_shardings=…, out_shardings=…).lower(...).compile()``,
  * record ``memory_analysis()`` (fits-on-chip proof), ``cost_analysis()``
    (FLOPs/bytes for §Roofline) and the per-device collective traffic parsed
    from the post-SPMD HLO.

Results append to a JSONL file consumed by ``repro.launch.roofline``.

Usage:
  python -m repro.launch.dryrun                       # all cells, both meshes
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --knob remat=none --knob rules_preset=tp --tag x
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import (
    ARCH_IDS,
    SHAPES,
    ModelConfig,
    ShapeSpec,
    get_config,
    shape_applicable,
)
from repro.data import batch_specs
from repro.dist.sharding import axis_rules, spec_for_shape
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import describe_mesh, make_production_mesh
from repro.models import Model
from repro.models.common import abstract_params, param_specs
from repro.optim import OptimizerConfig, opt_state_defs
from repro.train.step import RunKnobs, make_serve_step, make_train_step
from repro.utils.hlo import count_ops, parse_collectives
from repro.utils.hlo_cost import analyze_hlo

__all__ = ["input_specs", "run_cell", "main"]


def _spec_to_sharding(tree_specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    frontend = None
    if cfg.frontend or cfg.encoder:
        frontend = (cfg.frontend_tokens, cfg.frontend_dim)
    if shape.kind in ("train", "prefill"):
        return batch_specs(cfg.vocab_size, shape.seq_len, shape.global_batch,
                           frontend=frontend)
    # decode: one new token against a seq_len KV cache
    return {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32),
    }


def _batch_sharding(specs: Dict[str, Any], rules, mesh):
    out = {}
    for k, v in specs.items():
        axes = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = NamedSharding(mesh, spec_for_shape(v.shape, axes, rules, mesh))
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             knobs: RunKnobs = RunKnobs(),
             opt_cfg: OptimizerConfig = OptimizerConfig(),
             verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one cell; returns the roofline-input record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cfg_updates: Dict[str, Any] = {}
    if knobs.attn_impl:
        cfg_updates["attn_impl"] = knobs.attn_impl
    # explicit knob > autotune cache (if enabled) > ModelConfig default
    bq, bkv = knobs.resolved_attn_blocks(cfg, shape.seq_len)
    if bq != cfg.attn_block_q:
        cfg_updates["attn_block_q"] = bq
    if bkv != cfg.attn_block_kv:
        cfg_updates["attn_block_kv"] = bkv
    if knobs.pad_heads:
        cfg_updates["pad_heads_to_multiple"] = 16
    if cfg_updates:
        cfg = dataclasses.replace(cfg, **cfg_updates)
    ok, reason = shape_applicable(cfg, shape)
    record: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "knobs": dataclasses.asdict(knobs),
        "time": time.time(),
    }
    if not ok:
        record.update(status="skipped", reason=reason)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rules = knobs.axis_rules()
    model = Model(cfg)
    t0 = time.time()

    with axis_rules(rules, mesh):
        p_abs = model.abstract_params()
        p_shard = _spec_to_sharding(model.param_specs(rules, mesh), mesh)
        if shape.kind == "train":
            o_defs = opt_state_defs(model.param_defs())
            o_abs = abstract_params(o_defs)
            o_shard = _spec_to_sharding(param_specs(o_defs, rules, mesh), mesh)
            b_specs = input_specs(cfg, shape)
            b_shard = _batch_sharding(b_specs, rules, mesh)
            step = make_train_step(model, opt_cfg, knobs)
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1) if knobs.donate else (),
            )
            lowered = jitted.lower(p_abs, o_abs, b_specs)
        elif shape.kind == "prefill":
            b_specs = input_specs(cfg, shape)
            b_shard = _batch_sharding(b_specs, rules, mesh)
            c_defs = model.cache_defs(shape.global_batch, shape.seq_len)
            c_abs = abstract_params(c_defs)
            c_shard = _spec_to_sharding(param_specs(c_defs, rules, mesh), mesh)

            def prefill_step(params, batch, cache):
                return model.prefill(params, batch, cache)

            jitted = jax.jit(
                prefill_step,
                in_shardings=(p_shard, b_shard, c_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(2,) if knobs.donate else (),
            )
            lowered = jitted.lower(p_abs, b_specs, c_abs)
        else:  # decode
            t_specs = input_specs(cfg, shape)
            t_shard = _batch_sharding(t_specs, rules, mesh)
            c_defs = model.cache_defs(shape.global_batch, shape.seq_len)
            c_abs = abstract_params(c_defs)
            c_shard = _spec_to_sharding(param_specs(c_defs, rules, mesh), mesh)
            serve_step = make_serve_step(model)
            jitted = jax.jit(
                serve_step,
                in_shardings=(p_shard, c_shard, t_shard["tokens"]),
                out_shardings=(None, c_shard),
                donate_argnums=(1,) if knobs.donate else (),
            )
            lowered = jitted.lower(p_abs, c_abs, t_specs["tokens"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # ---- analyses --------------------------------------------------------
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    mem_per_device = None
    mem_details: Dict[str, float] = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "alias_size_in_bytes",
                         "generated_code_size_in_bytes"):
                if hasattr(ma, attr):
                    mem_details[attr] = float(getattr(ma, attr))
            mem_per_device = (
                mem_details.get("temp_size_in_bytes", 0.0)
                + mem_details.get("argument_size_in_bytes", 0.0)
                + mem_details.get("output_size_in_bytes", 0.0)
                - mem_details.get("alias_size_in_bytes", 0.0)
            )
    except Exception as e:  # CPU backend may not implement it
        mem_details["error"] = str(e)

    hlo = compiled.as_text()
    ops = count_ops(hlo)
    # trip-count-aware static analysis (XLA's cost_analysis counts while
    # bodies once — useless for scan-over-layers programs; see hlo_cost.py)
    st = analyze_hlo(hlo)

    record.update(
        status="ok",
        n_chips=int(n_chips),
        lower_seconds=t_lower,
        compile_seconds=t_compile,
        flops_per_device=st.flops,
        bytes_per_device=st.mem_bytes,
        boundary_bytes_per_device=st.bytes_accessed,
        collective_bytes_per_device=float(st.collective_bytes),
        collectives={k: dict(v) for k, v in st.collectives.items()},
        n_while=st.n_while,
        trip_counts=st.trip_counts,
        unresolved_trips=st.unresolved_trips,
        xla_flops_per_device=float(cost.get("flops", -1.0)),
        xla_bytes_per_device=float(cost.get("bytes accessed", -1.0)),
        hlo_ops=ops,
        memory_per_device_bytes=mem_per_device,
        memory_details=mem_details,
        hlo_chars=len(hlo),
    )
    if verbose:
        colls = ", ".join(
            f"{k}×{int(v['count'])} ({v['bytes'] / 2**20:.0f}MiB)"
            for k, v in sorted(st.collectives.items()))
        print(f"[dryrun] {arch} × {shape_name} × {record['mesh']}: "
              f"compile {t_compile:.1f}s, "
              f"flops/dev {st.flops:.3g}, "
              f"bytes/dev {st.bytes_accessed:.3g}, "
              f"mem/dev {0 if mem_per_device is None else mem_per_device / 2**30:.2f} GiB")
        print(f"  collectives/dev: {colls or 'none'}")
        print(f"  memory_analysis: {mem_details}")
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", action="append", choices=ARCH_IDS)
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="both")
    ap.add_argument("--knob", action="append", default=[],
                    help="RunKnobs override, e.g. --knob remat=none")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    knob_kwargs: Dict[str, Any] = {}
    for kv in args.knob:
        k, v = kv.split("=", 1)
        field_types = {f.name: f.type for f in dataclasses.fields(RunKnobs)}
        if k not in field_types:
            raise SystemExit(f"unknown knob {k!r}")
        cur = getattr(RunKnobs(), k)
        if isinstance(cur, bool):
            knob_kwargs[k] = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            knob_kwargs[k] = int(v)
        elif cur is None:
            knob_kwargs[k] = v
        else:
            knob_kwargs[k] = type(cur)(v)
    knobs = RunKnobs(**knob_kwargs)

    archs = args.arch or ARCH_IDS
    shapes = args.shape or list(SHAPES)
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[
        args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"], r.get("tag")))
                except json.JSONDecodeError:
                    pass

    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi_pod in meshes:
                mesh_name = "2x16x16" if multi_pod else "16x16"
                key = (arch, shape, mesh_name, args.tag)
                if key in done:
                    print(f"[dryrun] skip existing {key}")
                    continue
                try:
                    rec = run_cell(arch, shape, multi_pod=multi_pod,
                                   knobs=knobs)
                except Exception:
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_name,
                        "status": "error",
                        "error": traceback.format_exc(limit=20),
                    }
                    failures += 1
                    print(f"[dryrun] ERROR {arch} × {shape} × {mesh_name}:")
                    print(rec["error"])
                rec["tag"] = args.tag
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
    print(f"[dryrun] complete; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
