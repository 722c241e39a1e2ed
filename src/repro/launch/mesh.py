"""Production mesh + sharding-rule resolution.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (tests/benches must keep seeing 1 CPU device).
"""
from __future__ import annotations

import jax

from repro.models.config import ModelConfig
from repro.models.module import ShardingRules


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_rules(mesh, cfg: ModelConfig, *, seq_parallel: bool = False) -> ShardingRules:
    """Resolve logical-axis -> mesh-axis rules for this (mesh, arch).

    MoE: experts shard on "model" only when the expert count divides it
    (qwen3: 128/16 ok); otherwise (mixtral: 8 experts) experts stay replicated
    and the expert FFN is TP-sharded on d_ff.
    """
    batch = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    model_size = mesh.shape["model"]
    expert = "model"
    mlp = "model"
    if cfg.num_experts:
        if cfg.num_experts % model_size == 0:
            mlp = None      # EP: experts own the model axis; expert FFN local
        else:
            expert = None   # mixtral: 8 experts < 16 -> replicate experts, TP d_ff
    return ShardingRules(
        embed="data", vocab="model", heads="model", mlp=mlp,
        expert=expert, layers=None,
        seq="model" if seq_parallel else None, batch=batch)


def sanitize_spec(shape: tuple, spec, mesh) -> "P":
    """Drop sharding on dims the mesh cannot divide evenly (vocab 51865,
    batch 1, ...). For tuple entries keep the largest divisible prefix.
    Production frameworks pad instead; for lower+compile analysis dropping is
    equivalent and keeps the numbers honest."""
    from jax.sharding import PartitionSpec as P
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        kept = []
        prod = 1
        for n in names:
            if dim % (prod * mesh.shape[n]) == 0:
                kept.append(n)
                prod *= mesh.shape[n]
            else:
                break
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def sanitize_specs(abstract_tree, spec_tree, mesh):
    from jax.sharding import PartitionSpec as P
    return jax.tree.map(
        lambda a, s: sanitize_spec(a.shape, s, mesh),
        abstract_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, P))
