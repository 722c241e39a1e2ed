import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

Per cell this script:
  1. builds the production mesh (16x16 or 2x16x16),
  2. lowers the right step fn (train_step / prefill / decode_step) against
     ShapeDtypeStruct inputs with full NamedShardings,
  3. compiles, prints memory_analysis() (proves it fits) and cost_analysis()
     (FLOPs/bytes for the roofline),
  4. parses the HLO for collective operand bytes,
  5. appends a JSON record to --out.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]   # every runnable cell
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, cells
from repro.launch import specs as SP
from repro.launch.hlo import analyze
from repro.launch.mesh import (make_production_mesh, make_rules,
                               sanitize_spec, sanitize_specs)
from repro.models import model as Mdl
from repro.train import trainstep as TS
from repro.train.optimizer import OptConfig


def shard_tree(mesh, abstract_tree, spec_tree):
    """Sanitize (divisibility) then wrap in NamedShardings."""
    clean = sanitize_specs(abstract_tree, spec_tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), clean,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_rules(rules, mesh, global_batch):
    """Shrink the activation batch axes to what the batch size divides."""
    names = rules.batch if isinstance(rules.batch, tuple) else (rules.batch,)
    kept, prod = [], 1
    for n in names:
        if n and global_batch % (prod * mesh.shape[n]) == 0:
            kept.append(n)
            prod *= mesh.shape[n]
        else:
            break
    import dataclasses as _dc
    return _dc.replace(rules, batch=tuple(kept) if kept else None)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               overrides: dict | None = None, num_microbatches: int = 8):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    # Baseline production knobs: sequence-parallel activations for training
    # (saved residuals shard over the model axis -> 16x less live activation
    # memory under scan+remat); serving stays batch/seq-cache sharded.
    if shape.kind == "train":
        cfg = cfg.with_overrides(seq_parallel=True)
    if overrides:
        cfg = cfg.with_overrides(**overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(mesh, cfg, seq_parallel=cfg.seq_parallel)
    rules = _batch_rules(rules, mesh, shape.batch)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            oc = OptConfig()
            step = TS.make_train_step(cfg, rules, oc, num_microbatches)
            state = TS.abstract_state(cfg)
            sspecs = TS.state_specs(cfg, rules)
            batch, bspecs = SP.train_batch_specs(cfg, shape, rules)
            fn = jax.jit(step,
                         in_shardings=(shard_tree(mesh, state, sspecs),
                                       shard_tree(mesh, batch, bspecs)),
                         out_shardings=(shard_tree(mesh, state, sspecs), None),
                         donate_argnums=(0,))
            lowered = fn.lower(state, batch)
        elif shape.kind == "prefill":
            scfg = cfg.with_overrides(param_dtype="bfloat16")
            params = Mdl.abstract_params(scfg)
            pspecs = Mdl.param_specs(scfg, rules)
            inputs, ispecs = SP.prefill_specs(scfg, shape, rules)

            def fn(params, inputs):
                return Mdl.prefill(scfg, params, inputs["tokens"], rules=rules,
                                   frontend=inputs.get("frontend"))

            lowered = jax.jit(
                fn,
                in_shardings=(shard_tree(mesh, params, pspecs),
                              shard_tree(mesh, inputs, ispecs)),
            ).lower(params, inputs)
        else:  # decode
            scfg = cfg.with_overrides(param_dtype="bfloat16")
            params = Mdl.abstract_params(scfg)
            pspecs = Mdl.param_specs(scfg, rules)
            inputs, ispecs = SP.decode_specs(scfg, shape, rules)
            cache_sh = shard_tree(mesh, inputs["cache"], ispecs["cache"])

            def fn(params, cache, tokens):
                return Mdl.decode_step(scfg, params, cache, tokens, rules=rules)

            lowered = jax.jit(
                fn,
                in_shardings=(shard_tree(mesh, params, pspecs),
                              cache_sh,
                              NamedSharding(mesh, sanitize_spec(
                                  (shape.batch, 1), ispecs["tokens"], mesh))),
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            ).lower(params, inputs["cache"], inputs["tokens"])
    return cfg, mesh, lowered


# Per-arch baseline microbatch counts (train cells): chosen so the activation
# working set fits 16 GiB HBM at global batch 256 x 4k.
TRAIN_MICROBATCHES = {"deepseek-67b": 16}


def run_cell(arch, shape_name, *, multi_pod, out_path=None, overrides=None,
             num_microbatches=8, tag="baseline"):
    num_microbatches = TRAIN_MICROBATCHES.get(arch, num_microbatches)
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "tag": tag}
    try:
        cfg, mesh, lowered = lower_cell(arch, shape_name, multi_pod=multi_pod,
                                        overrides=overrides,
                                        num_microbatches=num_microbatches)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):   # old jax: one dict per device kind
            ca = ca[0] if ca else {}
        # multiplicity-aware HLO accounting (lax.scan bodies x trip count) —
        # XLA's own cost_analysis counts loop bodies once (kept as *_xla).
        acct = analyze(compiled.as_text())
        rec.update(
            ok=True, lower_s=round(t1 - t0, 1), compile_s=round(t2 - t1, 1),
            flops_per_device=acct["dot_flops"],
            bytes_per_device=acct["hbm_bytes"],
            collective_bytes_per_device=acct["collective_bytes"],
            collectives=acct["coll_by_op"],
            collective_counts=acct["coll_counts"],
            scope_bytes=acct["scope_bytes"],
            flops_xla_bodyonce=ca.get("flops", 0.0),
            bytes_xla_bodyonce=ca.get("bytes accessed", 0.0),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
            params=cfg.param_count(),
            active_params=cfg.active_param_count(),
        )
        print(f"[dryrun] {arch} {shape_name} {rec['mesh']} OK "
              f"compile={rec['compile_s']}s flops/dev={rec['flops_per_device']:.3e} "
              f"mem(temp)={ma.temp_size_in_bytes/2**30:.2f}GiB "
              f"coll={acct['collective_bytes']/2**20:.1f}MiB", flush=True)
    except Exception as e:  # a failing cell is a bug in the system
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        print(f"[dryrun] {arch} {shape_name} {rec['mesh']} FAIL {rec['error']}",
              flush=True)
    if out_path:
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf iterations)")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    overrides = json.loads(args.override) if args.override else None

    if args.all:
        ok = True
        for arch in ARCH_IDS:
            for shape_name in cells(arch):
                rec = run_cell(arch, shape_name, multi_pod=args.multi_pod,
                               out_path=args.out, overrides=overrides,
                               num_microbatches=args.microbatches, tag=args.tag)
                ok &= rec["ok"]
        raise SystemExit(0 if ok else 1)

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   out_path=args.out, overrides=overrides,
                   num_microbatches=args.microbatches, tag=args.tag)
    raise SystemExit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
