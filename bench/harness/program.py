"""The system under test: a model and its serve engine, built from a
configuration file through the program's normal objects (``get_config``,
``Model``, ``ServeConfig``, ``ServeEngine.generate``)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

from . import weights as weights_mod
from .counts import Dims

# the published config's keys as the program's ModelConfig names them
_WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "intermediate_size": "d_ff",
           "vocab_size": "vocab_size", "rope_theta": "rope_theta",
           "qkv_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings"}
_ACTS = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def model_config(name: str, cfg: Dict[str, Any]):
    """The registry's model at this file's depth and norm epsilon; every
    other field must already equal the file, or this raises."""
    from repro.configs import get_config

    base = get_config(cfg["program"]["registry"])
    eps = cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))
    mcfg = dataclasses.replace(base, name=name,
                               n_layers=cfg["num_hidden_layers"],
                               norm_eps=float(eps),
                               param_dtype=cfg["torch_dtype"],
                               compute_dtype=cfg["torch_dtype"])
    want = {field: cfg[key] for key, field in _WIDTHS.items()}
    want["activation"] = _ACTS[cfg["hidden_act"]]
    want["superblock"] = ("attn",)
    got = {k: getattr(mcfg, k) for k in want}
    if got != want or mcfg.padded_heads != mcfg.n_heads:
        raise ValueError(f"{name}: the program's {base.name} is not this "
                         f"file: {got} != {want}")
    return mcfg


def serve_config(cfg: Dict[str, Any]):
    from repro.serve import ServeConfig

    mesh = cfg.get("mesh")
    return ServeConfig(**cfg["serve"],
                       mesh_shape=tuple(mesh) if mesh else None)


class Program:
    """Model, weights and engine of one configuration."""

    def __init__(self, name: str, cfg: Dict[str, Any], seed: int):
        import jax

        from repro.models import Model

        self.cfg = cfg
        self.dims = Dims.from_config(cfg)
        self.model = Model(model_config(name, cfg))
        self.scfg = serve_config(cfg)
        tree = weights_mod.layout(self.dims, bool(cfg["qkv_bias"]),
                                  cfg["torch_dtype"])
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
