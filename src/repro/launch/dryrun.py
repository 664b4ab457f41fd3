import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

Per cell this driver:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. builds ShapeDtypeStruct stand-ins for params / optimizer / cache /
     batch (no allocation anywhere),
  3. lowers + compiles the step function —
       train_4k      → full train_step (fwd + bwd + AdamW/ZeRO-1),
       prefill_32k   → forward,
       decode_*      → decode_step (one token against the cache),
  4. prints compiled.memory_analysis() (fits-per-device proof) and
     cost_analysis() (FLOPs/bytes for §Roofline),
  5. parses collective bytes from the optimized HLO and writes the JSON
     consumed by benchmarks/bench_roofline.py and EXPERIMENTS.md.

Layers are *unrolled* here (``unroll=True``) so XLA's cost analysis counts
every layer — a `while` body is costed once, not ×trip-count.  Production
execution uses the scan form; both lower through identical per-layer HLO.

Hillclimbing knobs (used by §Perf): ``--attention naive`` reproduces the
paper's sequential/implicit-only baseline; ``--no-remat``, ``--no-zero1``,
``--accum`` toggle the corresponding optimisations.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..api import Session
from ..configs import SHAPES, get_config, list_archs
from ..configs.base import ArchConfig, ShapeSpec
from ..core.policy import CelloPlan
from ..models import decode_step, forward, set_mesh_context
from ..optim import AdamWConfig, adamw_init
from . import shardings as shd
from .mesh import make_production_mesh
from .roofline import model_flops, parse_collectives, roofline
from .train import TrainConfig, jit_train_step


#: the chip the production meshes stand for (the placeholder host devices
#: report the CPU, so the roofline is costed against the target's peaks)
TARGET_DEVICE_KIND = "TPU v5 lite"


def _plan_for(cfg: ArchConfig, shape: ShapeSpec, attention: str,
              ) -> CelloPlan:
    plan = Session(cfg).default_plan(seq=shape.seq_len).plan
    if attention == "naive":
        plan = dataclasses.replace(plan, use_flash_attention=False,
                                   use_fused_mlp=False,
                                   notes="seq-implicit baseline")
    return plan


def lower_cell(arch: str, shape_name: str, multi_pod: bool, *,
               attention: str = "flash", remat: bool = True,
               zero1: bool = True, accum: int = 1,
               kv_block: Optional[int] = None,
               cache_dus: bool = False,
               moe_cf: Optional[float] = None,
               serve_dtype: str = "f32") -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape_name not in cfg.supported_shapes():
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped",
                "reason": ("encoder-only: no decode step"
                           if cfg.encoder_only else
                           "full-attention arch: 500k decode skipped "
                           "(see DESIGN.md)")}
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh_context(mesh)
    n_chips = mesh.devices.size
    plan = _plan_for(cfg, shape, attention)
    if kv_block:
        plan = dataclasses.replace(plan, kv_block=kv_block)
    if cache_dus:
        plan = dataclasses.replace(plan, cache_select_update=False)
    if moe_cf is not None:
        plan = dataclasses.replace(plan, moe_capacity_factor=moe_cf)
    specs = shd.input_specs(cfg, shape, mesh)
    dt = (jnp.bfloat16 if serve_dtype == "bf16" and shape.mode == "decode"
          else None)
    params_sds, p_shardings = shd.params_for_split(cfg, mesh, dtype=dt)

    t0 = time.time()
    if shape.mode == "train":
        opt_cfg = AdamWConfig()
        train_cfg = TrainConfig(remat=remat, unroll=True, zero1=zero1,
                                accum_steps=accum, donate=True)
        from .train import zero1_shardings
        o_shardings = zero1_shardings(params_sds, p_shardings, mesh, zero1)
        opt_sds = shd.shaped(
            jax.eval_shape(lambda p: adamw_init(p), params_sds), o_shardings)
        batch = {k: v for k, v in specs.items()}
        fn = jit_train_step(cfg, plan, opt_cfg, mesh, train_cfg,
                            batch_specs=batch, p_shardings=p_shardings,
                            o_shardings=o_shardings)
        lowered = fn.lower(params_sds, opt_sds, batch)
    elif shape.mode == "prefill":
        def prefill(params, batch):
            logits, _ = forward(params, cfg, plan, batch["tokens"],
                                frames=batch.get("frames"),
                                img=batch.get("img"),
                                mode="prefill", unroll=True)
            return logits
        batch = dict(specs)
        b_shardings = jax.tree.map(lambda s: s.sharding, batch)
        out_sh = NamedSharding(mesh, P(None, None, "model"))
        lowered = jax.jit(prefill, in_shardings=(p_shardings, b_shardings),
                          out_shardings=out_sh).lower(params_sds, batch)
    else:  # decode
        cache_sds = specs["cache"]
        c_shardings = specs["cache_shardings"]

        def serve_step(params, cache, tokens, pos):
            return decode_step(params, cache, cfg, plan, tokens, pos,
                               unroll=True)
        logits_sh = NamedSharding(mesh, P(None, None, "model"))
        lowered = jax.jit(
            serve_step,
            in_shardings=(p_shardings, c_shardings,
                          specs["tokens"].sharding, NamedSharding(mesh, P())),
            out_shardings=(logits_sh, c_shardings),
            donate_argnums=(1,),
        ).lower(params_sds, cache_sds, specs["tokens"], specs["pos"])
    lower_s = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)                                   # proves it fits
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    terms = roofline(float(ca.get("flops", 0.0)),
                     float(ca.get("bytes accessed", 0.0)),
                     coll["total"], n_chips, model_flops(cfg, shape),
                     device_kind=TARGET_DEVICE_KIND)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok",
        "n_chips": n_chips,
        "attention": attention, "remat": remat, "zero1": zero1,
        "cache_dus": cache_dus,
        "accum": accum, "kv_block": plan.kv_block,
        "lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "cost": {"flops_per_chip": float(ca.get("flops", 0.0)),
                 "bytes_per_chip": float(ca.get("bytes accessed", 0.0))},
        "collectives": coll,
        "roofline": terms.to_dict(),
        "hlo_bytes": len(hlo),
    }
    return result


def run_cells(args) -> int:
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ({"single": [False], "multi": [True],
               "both": [False, True]})[args.mesh]
    os.makedirs(args.outdir, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tagpart = f"__{args.tag}" if args.tag else ""
                name = (f"{arch}__{shape}__"
                        f"{'multi' if multi else 'single'}{tagpart}.json")
                out_path = os.path.join(args.outdir, name)
                if args.skip_existing and os.path.exists(out_path):
                    print(f"[skip-existing] {name}")
                    continue
                print(f"=== {arch} × {shape} × "
                      f"{'multi' if multi else 'single'} ===", flush=True)
                try:
                    res = lower_cell(arch, shape, multi,
                                     attention=args.attention,
                                     remat=not args.no_remat,
                                     zero1=not args.no_zero1,
                                     accum=args.accum,
                                     kv_block=args.kv_block,
                                     cache_dus=args.cache_dus,
                                     moe_cf=args.moe_cf,
                                     serve_dtype=args.serve_dtype)
                except Exception as e:           # a failure here is a bug
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "error", "error": repr(e)}
                    failures += 1
                if res.get("status") == "ok":
                    r = res["roofline"]
                    print(f"  compute {r['compute_s']*1e3:9.3f} ms | "
                          f"memory {r['memory_s']*1e3:9.3f} ms | "
                          f"collective {r['collective_s']*1e3:9.3f} ms | "
                          f"dominant {r['dominant']}", flush=True)
                with open(out_path, "w") as f:
                    json.dump(res, f, indent=1)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--outdir", default="experiments/dryrun")
    ap.add_argument("--tag", default="",
                    help="suffix for §Perf hillclimb variants")
    ap.add_argument("--attention", choices=["flash", "naive"],
                    default="flash")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--kv-block", type=int, default=None)
    ap.add_argument("--cache-dus", action="store_true",
                    help="baseline: dynamic_update_slice cache writes")
    ap.add_argument("--moe-cf", type=float, default=None,
                    help="MoE capacity factor override")
    ap.add_argument("--serve-dtype", choices=["f32", "bf16"], default="f32",
                    help="param dtype for decode cells (serving precision)")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    failures = run_cells(args)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
