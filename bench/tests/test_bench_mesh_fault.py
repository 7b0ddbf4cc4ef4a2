"""The exchange between chips left out: a tensor-parallel (1, 4) run at
test size on four virtual CPU devices, once sound and once with the MLP's
all-reduce taken out (each chip keeps its own partial sum).  The device
count must be set before JAX starts, hence a subprocess."""
import json
import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

SCRIPT = textwrap.dedent("""
    import json, time
    import jax
    from jax.sharding import PartitionSpec as P
    import tiny
    from bench.harness import runner
    import repro.models.mlp as mlp_mod
    from repro.dist.sharding import current_rules

    def no_exchange(prog):
        def mlp(params, x, cfg):
            _, mesh = current_rules()

            def local(x, wi, wg, wo):
                h = jax.nn.silu(x @ wg) * (x @ wi)
                return h @ wo  # this chip's partial sum, never all-reduced

            return jax.shard_map(
                local, mesh=mesh,
                in_specs=(P(), P(None, "model"), P(None, "model"),
                          P("model", None)),
                out_specs=P(), check_vma=False)(
                    x, params["wi"], params["wg"], params["wo"])
        mlp_mod.mlp = mlp

    out = {}
    for tag, hook in (("sound", None), ("no_exchange", no_exchange)):
        r = runner.run_cell(tiny.cell(True, mesh=[1, 4]), 11, 0.0, False,
                            time.time(), program_hook=hook,
                            compile_cache=False)
        out[tag] = r["correct"]
    print(json.dumps(out))
""")


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [HERE, ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "no_exchange": False}
