import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Dry-run of the Gyges transformation ITSELF at pod scale.

Lowers + compiles the full weight + KV-pool reshard for a pod of
transformable instance groups: 256 chips as 64 hosts x (rep, tp) groups,
re-factorized (rep=4, tp=1) -> (rep=1, tp=4) per host — i.e. every host
simultaneously merging 4x(TP1) into TP4 (the paper's Fig. 3, 64 times in
parallel).  Reports the collective bytes of the transformation — with the
header-centric layout these are pure block-granular all-to-alls.

    PYTHONPATH=src python -m repro.launch.transform_dryrun \
        [--arch llama3-8b] [--tokens-per-seq 4096]
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.padding import make_plan
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_mesh
from repro.launch.specs import param_specs
from repro.core.instance import param_pspecs as inst_pspecs
from repro.models.model import PAGE_TOKENS

OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                   "experiments", "dryrun")


def run(arch: str, tokens_per_seq: int, batch_per_rep: int = 4):
    cfg = get_config(arch)
    plan = make_plan(cfg, 4, mode="page")
    # 256 chips = 64 hosts x 4 workers; host axis shards independent
    # instance groups, (rep, tp) is the transformable factorization.
    mesh_tp1 = make_mesh((64, 4, 1), ("host", "rep", "tp"))
    mesh_tp4 = make_mesh((64, 1, 4), ("host", "rep", "tp"))

    # ---- weights: replicated per host at TP1 -> column/row sharded ------
    p_sds = param_specs(cfg, plan)
    pspecs = inst_pspecs(p_sds, transform_attn=True)
    in_sh = jax.tree.map(lambda ps: NamedSharding(mesh_tp1, ps), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    out_sh = jax.tree.map(lambda ps: NamedSharding(mesh_tp4, ps), pspecs,
                          is_leaf=lambda x: isinstance(x, P))

    # ---- KV pools: one attention layer group's pool per host ------------
    n_attn = sum(1 for k in cfg.pattern if k in ("attn", "sliding", "moe"))
    B = 4 * batch_per_rep
    mps = tokens_per_seq // PAGE_TOKENS
    pool_sds = jax.ShapeDtypeStruct(
        (n_attn, B * mps, plan.kv_slots, 2, PAGE_TOKENS,
         cfg.resolved_head_dim), jnp.bfloat16)
    pool_in = NamedSharding(mesh_tp1, P(None, ("host", "rep"), "tp"))
    pool_out = NamedSharding(mesh_tp4, P(None, ("host", "rep"), "tp"))

    def transform(params, pool):
        params = jax.lax.with_sharding_constraint(params, out_sh)
        pool = jax.lax.with_sharding_constraint(pool, pool_out)
        return params, pool

    t0 = time.time()
    lowered = jax.jit(transform,
                      in_shardings=(in_sh, pool_in),
                      out_shardings=(out_sh, pool_out),
                      donate_argnums=(0, 1)).lower(p_sds, pool_sds)
    compiled = lowered.compile()
    coll = collective_bytes(compiled.as_text())
    total = sum(v for k, v in coll.items() if k != "count")
    weight_bytes = cfg.param_count() * 2
    pool_bytes = 1
    for d in pool_sds.shape:
        pool_bytes *= d
    pool_bytes *= 2
    rec = {
        "arch": arch, "mesh": "64 hosts x (rep,tp)",
        "direction": "64x[4x(TP1) -> TP4]",
        "compile_s": round(time.time() - t0, 1),
        "collective_bytes_per_device": total,
        "collective_ops": coll["count"],
        "weights_bytes_global": weight_bytes,
        "kv_pool_bytes_global_per_host": pool_bytes,
        "est_time_ms_at_ici": total / 50e9 * 1e3,
    }
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"transform_{arch}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=ASSIGNED_ARCHS)
    ap.add_argument("--tokens-per-seq", type=int, default=4096)
    args = ap.parse_args()
    rec = run(args.arch, args.tokens_per_seq)
    print(f"OK transform {rec['arch']}: compile={rec['compile_s']}s "
          f"coll={rec['collective_bytes_per_device']:.3e} B/dev "
          f"({rec['collective_ops']} ops) "
          f"~{rec['est_time_ms_at_ici']:.1f} ms at ICI bw")


if __name__ == "__main__":
    main()
