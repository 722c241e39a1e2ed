"""Explicit expert-parallel MoE via shard_map (the collective-lean path).

The dense/GSPMD formulation (``layers.moe_apply``) lets the SPMD partitioner
reshard the (tokens x experts) scatter/gather — measured at ~24 TB of
all-gather/all-reduce per device per step on qwen3 (48L x 8mb). This
implementation pins the data movement by construction:

* tokens are *replicated over the model axis* (they are only batch-sharded),
  so every model shard routes every local token — router flops are tiny;
* each model shard owns ``E / model`` experts and builds a LOCAL
  (E_loc, C_loc, D) dispatch buffer — no collective;
* expert weights are FSDP-sharded on D over the data axis; one explicit
  ``all_gather`` per layer recovers them (grads flow back as psum-scatter);
* the only cross-shard traffic for activations is ONE bf16 ``psum`` of the
  (T_loc, D) combine over the model axis — same size as a TP all-reduce.

Per layer per microbatch: psum(B_loc*S*D*2B) + weight gather — vs the dense
path's token-matrix all-gathers. See EXPERIMENTS.md §Perf cell A.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .module import ShardingRules


def _local_moe(xt, router, gate, up, down, *, cfg: ModelConfig, model_axis,
               data_axes, n_model: int):
    """Body runs per (data, model) shard. xt: (T_loc, D) tokens (replicated
    over model). gate/up/down: (E_loc, D_loc, F) FSDP shards."""
    dt = jnp.dtype(cfg.compute_dtype)
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    e_loc = E // n_model
    my_first = jax.lax.axis_index(model_axis) * e_loc

    # FSDP: recover full-D expert weights for the experts this shard owns.
    if data_axes:
        gate = jax.lax.all_gather(gate, data_axes, axis=1, tiled=True)
        up = jax.lax.all_gather(up, data_axes, axis=1, tiled=True)
        down = jax.lax.all_gather(down, data_axes, axis=1, tiled=True)

    t_loc, D = xt.shape
    logits = jnp.einsum("td,de->te", xt, router.astype(dt)).astype(jnp.float32)
    gates, idx = jax.lax.top_k(logits, K)                    # (T_loc, K)
    gates = jax.nn.softmax(gates, axis=-1)

    cap = max(8, int(cfg.capacity_factor * t_loc * K / E))
    flat_e = idx.reshape(-1)                                 # (T_loc*K,)
    rel = flat_e - my_first                                  # local expert id
    mine = (rel >= 0) & (rel < e_loc)
    rel_c = jnp.clip(rel, 0, e_loc - 1)
    onehot = jax.nn.one_hot(rel_c, e_loc, dtype=jnp.int32) * mine[:, None]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(pos, rel_c[:, None], axis=1)[:, 0]
    keep = mine & (slot < cap)
    slot = jnp.where(keep, slot, cap - 1)

    src = jnp.repeat(jnp.arange(t_loc), K)
    disp = jnp.zeros((e_loc, cap, D), dt).at[rel_c, slot].add(
        jnp.where(keep[:, None], xt[src], 0).astype(dt), mode="drop")

    g = jnp.einsum("ecd,edf->ecf", disp, gate.astype(dt))
    u = jnp.einsum("ecd,edf->ecf", disp, up.astype(dt))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, down.astype(dt))

    gathered = out[rel_c, slot] * keep[:, None]              # (T_loc*K, D)
    w = gates.reshape(-1)[:, None].astype(dt)
    partial = (gathered * w).reshape(t_loc, K, D).sum(axis=1)
    return jax.lax.psum(partial, model_axis)                 # (T_loc, D)


def moe_apply_ep(p, x, cfg: ModelConfig, rules: ShardingRules):
    """shard_map expert-parallel MoE. Requires an ambient mesh whose model
    axis divides num_experts; falls back to the dense path otherwise."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        from . import layers as L
        return L.moe_apply_dense(p, x, cfg, rules)
    n_model = mesh.shape["model"]
    if cfg.num_experts % n_model != 0:
        from . import layers as L
        return L.moe_apply_dense(p, x, cfg, rules)

    b, s, D = x.shape
    dt = jnp.dtype(cfg.compute_dtype)
    batch_axes = rules.batch if isinstance(rules.batch, tuple) else (
        (rules.batch,) if rules.batch else ())
    data_axes = rules.embed if rules.embed else None   # FSDP axis of weights

    body = functools.partial(
        _local_moe, cfg=cfg, model_axis="model",
        data_axes=data_axes, n_model=n_model)

    wspec = P("model", rules.embed, None)    # (E, D, F): EP on E, FSDP on D
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes if batch_axes else None, None),
                  P(None, None),            # router replicated (D x E, ~1 MB)
                  wspec, wspec, wspec),
        out_specs=P(batch_axes if batch_axes else None, None),
        check_vma=False,
    )
    xt = x.reshape(b * s, D).astype(dt)
    out = fn(xt, p["router"], p["gate"], p["up"], p["down"])
    return out.reshape(b, s, D)
