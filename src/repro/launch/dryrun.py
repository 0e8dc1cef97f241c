import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, with zero real allocation (ShapeDtypeStructs).

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod] [--decode-mode tp1] [--variant N]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Outputs one JSON per combo under experiments/dryrun/ containing
memory_analysis, cost_analysis, and collective-byte counts (for the
roofline).  ``--variant N`` compiles the *unrolled* N-group model used by
the roofline extrapolation (cost_analysis does not scale while-loop trip
counts)."""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, ASSIGNED_ARCHS, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.padding import make_plan
from repro.launch import sharding as SH
from repro.launch import specs as SP
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import (batch_axes, make_mesh, make_production_mesh,
                               model_axis_size)
from repro.models import model as M
from repro.training.optimizer import adamw
from repro.training.train_step import make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def variant_config(cfg: ModelConfig, n_units: int) -> ModelConfig:
    """Reduced-depth unrolled variant for cost extrapolation."""
    unit = cfg.layer_pattern if cfg.layer_pattern else (cfg.pattern[:1])
    return dataclasses.replace(cfg, num_layers=n_units * len(unit))


def build(cfg: ModelConfig, shape: ShapeConfig, mesh, decode_mode: str,
          unroll: bool, identity_pages: bool = False,
          moe_hints=False, banded: bool = False):
    plan = make_plan(cfg, model_axis_size(mesh), mode="lane")
    baxes = batch_axes(mesh)
    data_size = 1
    for a in baxes:
        data_size *= mesh.shape[a]

    p_sds = SP.param_specs(cfg, plan)
    fsdp = shape.kind == "train"
    em = moe_hints if moe_hints in ("dp", "tp") else "auto"
    p_ps = SH.param_pspecs(p_sds, cfg, plan, fsdp=fsdp,
                           data_size=mesh.shape["data"],
                           expert_mode=em)
    p_sh = SH.to_shardings(mesh, p_ps)
    in_sds = SP.model_inputs(cfg, shape)
    b_ps = SH.batch_pspecs(in_sds, mesh, baxes)
    b_sh = SH.to_shardings(mesh, b_ps)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    if shape.kind == "train":
        opt_init, opt_update = adamw(1e-3)
        o_sds = SP.opt_specs(p_sds)
        o_ps = SH.opt_pspecs(p_ps)
        o_sh = SH.to_shardings(mesh, o_ps)
        step = make_train_step(cfg, plan, opt_update,
                               unroll=unroll)

        def fn(params, opt_state, batch):
            return step(params, opt_state, batch)

        jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        args = (p_sds, o_sds, in_sds)
        return jitted, args

    if shape.kind == "prefill":
        c_sds = SP.cache_specs(cfg, plan, shape)
        c_ps = SH.cache_pspecs(c_sds, mesh, baxes, shape.global_batch,
                               decode_mode)
        c_sh = {k: SH.to_shardings(mesh, v) for k, v in c_ps.items()}

        def fn(params, batch, caches):
            return M.prefill(params, cfg, plan, batch, caches,
                             unroll=unroll, banded=banded)

        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh, c_sh),
                         out_shardings=(None, c_sh), donate_argnums=(2,))
        return jitted, (p_sds, in_sds, c_sds)

    # decode
    c_sds = SP.cache_specs(cfg, plan, shape)
    c_ps = SH.cache_pspecs(c_sds, mesh, baxes, shape.global_batch,
                           decode_mode)
    c_sh = {k: SH.to_shardings(mesh, v) for k, v in c_ps.items()}
    tok_sh = SH.to_shardings(
        mesh, SH.batch_pspecs(in_sds, mesh, baxes))

    def fn(params, caches, tokens, positions):
        return M.decode_step(params, cfg, plan, caches, tokens, positions,
                             unroll=unroll, identity_pages=identity_pages)

    jitted = jax.jit(
        fn, in_shardings=(p_sh, c_sh, tok_sh["tokens"],
                          tok_sh["positions"]),
        out_shardings=(None, c_sh), donate_argnums=(1,))
    return jitted, (p_sds, c_sds, in_sds["tokens"], in_sds["positions"])


def run_one(arch: str, shape_name: str, multi_pod: bool,
            decode_mode: str = "tp", variant: int = 0,
            save: bool = True, identity_pages: bool = False,
            moe_hints: bool = False, kv_hint: bool = False,
            banded: bool = False, mesh_shape=None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, note = SP.supports_shape(cfg, shape)
    tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}" + (
        f"_v{variant}" if variant else "") + (
        f"_{decode_mode}" if decode_mode != "tp" else "") + (
        "_idpages" if identity_pages else "") + (
        f"_moehints{moe_hints if moe_hints != True else ''}"
        if moe_hints else "") + (
        "_kvhint" if kv_hint else "") + ("_banded" if banded else "") + (
        f"_mesh{mesh_shape[0]}x{mesh_shape[1]}" if mesh_shape else "")
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "skipped": True,
               "reason": note}
        _save(tag, rec, save)
        return rec
    if shape.name == "long_500k":
        cfg = SP.long_context_variant(cfg)
    if variant:
        cfg = variant_config(cfg, variant)

    if mesh_shape is not None:
        # §Perf: alternative (data, model) factorization of the same 256
        # chips — the Gyges thesis (lower TP when possible) at pod scale.
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    jitted, args = build(cfg, shape, mesh, decode_mode,
                         unroll=bool(variant),
                         identity_pages=identity_pages,
                         moe_hints=moe_hints, banded=banded)
    import contextlib
    from repro.launch.sharding import decide_expert_mode, moe_hint_specs
    from repro.models import shardhints
    hint_kw = {}
    if moe_hints and cfg.moe is not None:
        if moe_hints in ("dp", "tp"):
            em = moe_hints
        else:
            em = decide_expert_mode(cfg,
                                    make_plan(cfg, model_axis_size(mesh)),
                                    mesh.shape["data"])
        hint_kw.update(moe_hint_specs(em, mesh.shape["data"]))
    if kv_hint and shape.kind == "decode":
        from jax.sharding import PartitionSpec as PS
        baxes = [a for a in ("pod", "data") if a in mesh.axis_names]
        nb = 1
        for a in baxes:
            nb *= mesh.shape[a]
        bax = tuple(baxes) if shape.global_batch % nb == 0             and shape.global_batch >= nb else None
        hint_kw["decode_kv"] = PS(bax, None, None, "model", None)
    hctx = shardhints.hints(**hint_kw) if hint_kw else         contextlib.nullcontext()
    with mesh, hctx:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # older jax: one dict per device
        cost = cost[0] if cost else {}
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    n_dev = mesh.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "decode_mode": decode_mode, "variant": variant,
        "note": note,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_total": float(cost.get("flops", -1.0)),
        "bytes_accessed_total": float(cost.get("bytes accessed", -1.0)),
        "collectives": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "devices": n_dev,
    }
    _save(tag, rec, save)
    return rec


def _save(tag: str, rec: Dict[str, Any], save: bool) -> None:
    if not save:
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--decode-mode", default="tp", choices=["tp", "tp1"])
    ap.add_argument("--variant", type=int, default=0,
                    help="unrolled N-group roofline variant (0 = full)")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in combos:
        try:
            rec = run_one(arch, shape, args.multi_pod, args.decode_mode,
                          args.variant)
            if rec.get("skipped"):
                print(f"SKIP  {arch:26s} {shape:12s} {rec['reason'][:60]}")
            else:
                print(f"OK    {arch:26s} {shape:12s} "
                      f"mesh={rec['mesh']:8s} "
                      f"compile={rec['compile_s']:6.1f}s "
                      f"flops={rec['flops_total']:.3e} "
                      f"coll_bytes={sum(v for k, v in rec['collectives'].items() if k != 'count'):.3e}")
        except Exception as e:
            failures += 1
            print(f"FAIL  {arch:26s} {shape:12s} {type(e).__name__}: {e}")
            traceback.print_exc(limit=3)
    if failures:
        raise SystemExit(f"{failures} dry-run failures")


if __name__ == "__main__":
    main()
