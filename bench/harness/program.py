"""The system under test: a model and its serve engine, built from a
configuration file through the program's normal objects (``get_config``,
``Model``, ``ServeConfig``, ``ServeEngine.generate``).  What is particular
to the model comes from the file's family (``bench/families``)."""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import spec
from . import weights as weights_mod


def serve_config(cfg: Dict[str, Any]):
    from repro.serve import ServeConfig

    mesh = cfg.get("mesh")
    return ServeConfig(**cfg["serve"],
                       mesh_shape=tuple(mesh) if mesh else None)


class Program:
    """Model, weights and engine of one configuration."""

    def __init__(self, name: str, cfg: Dict[str, Any], seed: int,
                 bench_dir: str = spec.BENCH_DIR):
        import jax

        from repro.models import Model

        self.cfg = cfg
        self.family = spec.family(cfg, bench_dir)
        self.dims = self.family.dims(cfg)
        self.model = Model(self.family.program_config(name, cfg))
        self.scfg = serve_config(cfg)
        tree = self.family.layout(cfg)
        want = jax.tree_util.tree_map(
            lambda s: (s.shape, s.dtype), self.model.abstract_params())
        have = jax.tree_util.tree_map(
            lambda s: (s.shape, s.dtype), weights_mod.shapes(tree))
        if want != have:
            raise ValueError("the benchmark's weight layout is not the "
                             "program's parameter tree")
        self.weights = weights_mod.drawer(
            tree, self._out_shardings())(weights_mod.root_key(seed))
        jax.block_until_ready(self.weights)
        self.engine = None

    def _out_shardings(self):
        """Under a mesh, each leaf is drawn where the engine keeps it, so
        no chip ever holds the whole model."""
        mesh = self.cfg.get("mesh")
        if not mesh:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.dist.sharding import RULE_PRESETS
        from repro.launch.mesh import make_mesh

        m = make_mesh(*mesh)
        specs = self.model.param_specs(RULE_PRESETS[self.scfg.rules_preset],
                                       m)
        import jax

        flat, treedef = jax.tree_util.tree_flatten(
            self.model.abstract_params())
        sflat = treedef.flatten_up_to(specs)
        return jax.tree_util.tree_unflatten(
            treedef, [NamedSharding(m, PartitionSpec(*s)) for s in sflat])

    @property
    def model_shards(self) -> int:
        """Chips that share each layer: the mesh's model axis."""
        mesh = self.cfg.get("mesh")
        return int(mesh[1]) if mesh else 1

    def start(self):
        from repro.serve import ServeEngine

        self.engine = ServeEngine(self.model, self.weights, self.scfg)
        return self.engine

    def generate(self, prompts: List[List[int]], max_new: List[int]):
        return self.engine.generate(prompts, max_new)

    def stop(self) -> None:
        """Drop the engine and its state; the weights stay for the
        reference."""
        self.engine = None

    def warm_prompts(self, shapes: List[Tuple[int, int]]
                     ) -> Tuple[List[List[int]], List[int]]:
        """One request per prefill-chunk length the mix produces (the
        program compiles one prefill per chunk length), each a prompt
        length taken from the mix, 3 new tokens: every decode and prefill
        shape the window uses, and no other.  Under a mesh the set
        repeats past the slot count, so that prefill and decode also run
        on a pool that earlier steps have written (another input
        sharding)."""
        chunk = self.scfg.prefill_chunk
        need = {}
        for p, _ in sorted(shapes):
            for c in _chunk_lengths(p, chunk):
                need.setdefault(c, p)
        lens = sorted(set(need.values()))
        if self.cfg.get("mesh"):
            reps = -(-(self.scfg.batch_slots + 1) // len(lens))
            lens = (lens * reps)[:max(len(lens), self.scfg.batch_slots + 1)]
        prompts = [[1 + (i % 7)] * p for i, p in enumerate(lens)]
        return prompts, [3] * len(prompts)


def _chunk_lengths(prompt_len: int, chunk: int) -> List[int]:
    full, rest = divmod(prompt_len, chunk)
    return [chunk] * full + ([rest] if rest else [])
