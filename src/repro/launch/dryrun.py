import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) cell this lowers AND
compiles the real step function -- train_step for train shapes,
prefill/decode serve steps for inference shapes -- against 256 (single
pod, 16x16) or 512 (2 pods, 2x16x16) placeholder host devices, then
records:

  * memory_analysis()      -> bytes per device (does it fit 16 GB HBM?)
  * cost_analysis()        -> per-device HLO FLOPs / bytes
  * optimized HLO          -> per-device collective bytes by type
  * the 3-term roofline + MODEL_FLOPS ratio (see repro/roofline/model.py)

Artifacts: one JSON per cell under --out (default artifacts/dryrun/).
Inputs are ShapeDtypeStructs end to end -- no array is ever allocated.

NOTE: the XLA_FLAGS line above MUST run before any other import (jax
locks the device count on first init); do not move it, and do not set
this flag anywhere global (tests and benches must see 1 device).
"""

import argparse
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, param_count, shape_applicable
from repro.launch.mesh import make_production_mesh, mesh_desc
from repro.models.lm import LM
from repro.optim import AdamWConfig, init_opt_state
from repro.parallel.sharding import make_plan
from repro.roofline import (
    RooflineReport, collective_bytes, model_flops_estimate,
)
from repro.roofline.hlo_analysis import analyze as hlo_analyze
from repro.train.step import make_train_step


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _mem_analysis(compiled) -> Dict[str, Optional[float]]:
    out: Dict[str, Optional[float]] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    for attr in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        out[attr] = float(getattr(ma, attr)) if ma is not None and hasattr(ma, attr) else None
    if out.get("argument_size_in_bytes") is not None:
        args = out["argument_size_in_bytes"] or 0.0
        tmp = out["temp_size_in_bytes"] or 0.0
        outb = out["output_size_in_bytes"] or 0.0
        alias = out["alias_size_in_bytes"] or 0.0
        out["peak_bytes_per_device"] = args + tmp + outb - alias
    else:
        out["peak_bytes_per_device"] = None
    return out


def _cost_analysis(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
        return {k: float(v) for k, v in ca.items() if np.isscalar(v)}
    except Exception:
        return {}


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline"):
    """Build + lower + compile one cell; returns (report_dict, compiled)."""
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch_name, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": True, "reason": why}, None

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    remat = "full" if shape.kind == "train" else "none"
    plan = make_plan(cfg, mesh, kind=shape.kind)
    lm = LM(cfg, remat=remat, chunk_q=512, loss_chunk=512,
            attn_seq_shard=(plan.attn_mode == "seq"))

    B, S = shape.global_batch, shape.seq_len
    # patches/meta tokens count toward the seq budget: cache is exactly S
    n_text = S - cfg.prefix_tokens - cfg.meta_tokens
    tok_spec = jax.ShapeDtypeStruct((B, n_text), jnp.int32)
    pe_spec = None
    if cfg.modality == "vision_stub":
        pe_spec = jax.ShapeDtypeStruct(
            (B, cfg.prefix_tokens, cfg.d_model), jnp.float32
        )

    params_abs = lm.abstract_params()
    t0 = time.perf_counter()

    with mesh:
        if shape.kind == "train":
            opt_abs = jax.eval_shape(init_opt_state, params_abs)
            step, _ = make_train_step(lm, plan, AdamWConfig())
            args = [params_abs, opt_abs, tok_spec]
            if pe_spec is not None:
                args.append(pe_spec)
            lowered = step.lower(*args)
        elif shape.kind == "prefill":
            pspecs = plan.param_specs(params_abs)
            in_sh = [
                jax.tree_util.tree_map(
                    lambda s: _ns(mesh, s), pspecs,
                    is_leaf=lambda x: isinstance(x, P),
                ),
                _ns(mesh, plan.batch_spec(2)),
            ]
            args = [params_abs, tok_spec]
            if pe_spec is not None:
                in_sh.append(_ns(mesh, plan.batch_spec(3)))
                args.append(pe_spec)
            cache_abs = jax.eval_shape(
                lambda: lm.init_cache(B, S)
            )
            cache_sh = jax.tree_util.tree_map(
                lambda s: _ns(mesh, s), plan.cache_specs(cache_abs),
                is_leaf=lambda x: isinstance(x, P),
            )
            # pin the emitted KV cache to its serving layout (seq-sharded);
            # otherwise GSPMD may materialise it replicated (29 GiB/device
            # on musicgen prefill_32k; see §Perf)
            fn = jax.jit(
                lambda p, t, pe=None: lm.prefill(p, t, S, pe),
                in_shardings=tuple(in_sh),
                out_shardings=(None, cache_sh, None),
            )
            lowered = fn.lower(*args)
        else:  # decode
            cache_abs = lm.abstract_cache(B, S)
            pspecs = plan.param_specs(params_abs)
            cspecs = plan.cache_specs(cache_abs)
            tok1 = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            len_spec = jax.ShapeDtypeStruct((B,), jnp.int32)
            in_sh = (
                jax.tree_util.tree_map(
                    lambda s: _ns(mesh, s), pspecs,
                    is_leaf=lambda x: isinstance(x, P),
                ),
                _ns(mesh, P(None, None)),
                jax.tree_util.tree_map(
                    lambda s: _ns(mesh, s), cspecs,
                    is_leaf=lambda x: isinstance(x, P),
                ),
                _ns(mesh, P(None)),
            )
            cache_sh = jax.tree_util.tree_map(
                lambda s: _ns(mesh, s), cspecs,
                is_leaf=lambda x: isinstance(x, P),
            )
            fn = jax.jit(
                lm.decode_step, in_shardings=in_sh, donate_argnums=(2,),
                out_shardings=(None, cache_sh, None),
            )
            lowered = fn.lower(params_abs, tok1, cache_abs, len_spec)

        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    cost = _cost_analysis(compiled)
    mem = _mem_analysis(compiled)
    hlo = compiled.as_text()
    census = hlo_analyze(hlo)  # trip-count-aware (see hlo_analysis.py)

    counts = param_count(cfg)
    mf = model_flops_estimate(cfg, shape, counts["active"])
    report = RooflineReport(
        arch=arch_name, shape=shape_name,
        mesh="multi" if multi_pod else "single", chips=chips,
        flops_per_device=census.flops,
        bytes_per_device=census.hbm_bytes,
        coll_bytes_per_device=census.collective_bytes,
        model_flops=mf,
        peak_memory_per_device=mem.get("peak_bytes_per_device"),
        coll_breakdown={k: int(v) for k, v in census.coll_breakdown.items()},
    )
    out = report.to_dict()
    out.update({
        "variant": variant,
        "skipped": False,
        "attn_mode": plan.attn_mode,
        "t_lower_s": t_lower,
        "t_compile_s": t_compile,
        "memory_analysis": mem,
        # raw cost_analysis kept for reference; it counts while bodies
        # once, hence the trip-count-aware census above (EXPERIMENTS.md)
        "xla_cost_analysis_flops": cost.get("flops"),
        "xla_cost_analysis_bytes": cost.get("bytes accessed"),
        "while_trip_counts": census.while_trips,
        "params_total": counts["total"],
        "params_active": counts["active"],
        "hlo_bytes": len(hlo),
    })
    return out, compiled


def cell_id(arch: str, shape: str, mesh: str, variant: str) -> str:
    return f"{arch}__{shape}__{mesh}" + ("" if variant == "baseline" else f"__{variant}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (or --all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="run the full matrix")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    archs = sorted(ARCHS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mname = "multi" if mp else "single"
                cid = cell_id(arch, shape, mname, args.variant)
                path = os.path.join(args.out, cid + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip existing] {cid}")
                    continue
                print(f"[dryrun] {cid} ...", flush=True)
                try:
                    report, compiled = lower_cell(arch, shape, mp, args.variant)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((cid, repr(e)))
                    report = {
                        "arch": arch, "shape": shape, "mesh": mname,
                        "variant": args.variant, "error": repr(e),
                    }
                    compiled = None
                with open(path, "w") as f:
                    json.dump(report, f, indent=1)
                if report.get("skipped"):
                    print(f"  -> SKIPPED: {report['reason']}")
                elif "error" in report:
                    print(f"  -> ERROR: {report['error']}")
                else:
                    print(
                        f"  -> ok  compile {report['t_compile_s']:.1f}s  "
                        f"bottleneck {report['bottleneck']}  "
                        f"t=({report['t_compute_s']:.2e},"
                        f"{report['t_memory_s']:.2e},"
                        f"{report['t_collective_s']:.2e})s  "
                        f"mem/dev "
                        f"{(report['memory_analysis']['peak_bytes_per_device'] or 0)/2**30:.2f}GiB"
                    )
                del compiled
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for cid, err in failures:
            print(f"  {cid}: {err}")
        return 1
    print("\nall requested cells passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
