"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 100 [--batch 256 --seq 4096] [--mesh 16,16] \
        [--ckpt-dir /path --ckpt-every 50] [--smoke]

On a real TPU slice this shards over the production mesh (FSDP x TP,
remat on, WSD schedule, AdamW); `--smoke` runs the reduced config on
whatever devices exist (CI uses 1 CPU device).  Resumes from the latest
checkpoint in --ckpt-dir if present.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.padding import make_plan
from repro.launch import sharding as SH
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.training import (DataConfig, SyntheticStream, adamw,
                            make_train_step, wsd)
from repro.training import checkpoint as ckpt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b",
                    choices=ASSIGNED_ARCHS + ["qwen2.5-32b"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None,
                    help="data,model — omit for single-device/smoke")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()

    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model"))
        plan = make_plan(cfg, shape[1], mode="lane")
    else:
        plan = make_plan(cfg, 1)

    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch} x {args.seq}, "
          f"devices={len(jax.devices())}")

    params = M.init_params(jax.random.PRNGKey(0), cfg, plan)
    sched = wsd(args.lr, warmup=max(args.steps // 20, 1),
                stable=args.steps // 2, decay=args.steps)
    opt_init, opt_update = adamw(sched)
    opt_state = opt_init(params)
    start_step = 0

    if args.ckpt_dir and os.path.exists(
            os.path.join(args.ckpt_dir, "index.json")):
        tree, start_step = ckpt.restore(args.ckpt_dir)
        params, opt_state = tree["params"], tree["opt"]
        print(f"[train] resumed from step {start_step}")

    step_fn = make_train_step(cfg, plan, opt_update)
    if mesh is not None:
        p_ps = SH.param_pspecs(params, cfg, plan, fsdp=True,
                               data_size=mesh.shape["data"])
        p_sh = SH.to_shardings(mesh, p_ps)
        o_sh = SH.to_shardings(mesh, SH.opt_pspecs(p_ps))
        step_fn = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                          out_shardings=(p_sh, o_sh, None),
                          donate_argnums=(0, 1))
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    data = SyntheticStream(DataConfig(cfg.vocab_size, args.seq,
                                      args.batch, seed=0))
    t0 = time.time()
    ctx = mesh or _null()
    with ctx:
        for i in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:6d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time()-t0)/max(i-start_step+1,1):.2f}s/it)")
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir,
                          {"params": params, "opt": opt_state}, step=i + 1)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, {"params": params, "opt": opt_state},
                  step=args.steps)
        print(f"[train] final checkpoint at {args.ckpt_dir}")


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
