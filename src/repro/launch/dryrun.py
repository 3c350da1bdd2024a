import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax -----------------------------------
import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as PS  # noqa: E402

from repro.configs import registry                    # noqa: E402
from repro.configs.base import SHAPES_BY_NAME         # noqa: E402
from repro.launch import roofline as RL               # noqa: E402
from repro.launch import specs as SP                  # noqa: E402
from repro.launch.mesh import make_production_mesh    # noqa: E402
from repro.models.zoo import build_model, model_flops_per_token  # noqa: E402
from repro.serve.decode import make_serve_step, make_prefill_step  # noqa: E402
from repro.sharding.rules import make_strategy        # noqa: E402
from repro.train import state as TS                   # noqa: E402
from repro.train.step import make_train_step          # noqa: E402
from repro.configs.base import TrainConfig            # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# bf16 TP-16 weights above this no longer fit a v5e chip alongside the KV
# cache -> serve weight-gathered (DESIGN.md §4).
_SERVE_WG_BYTES = 12e9


def _mem_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_per_device"] = (out.get("argument_size_in_bytes", 0)
                                   + out.get("temp_size_in_bytes", 0)
                                   + out.get("output_size_in_bytes", 0)
                                   - out.get("alias_size_in_bytes", 0))
    return out


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, PS))


def run_cell(arch: str, shape_name: str, multi_pod: bool, strategy_name: str,
             remat: str = "full") -> dict:
    cfg = registry.get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = registry.cell_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "strategy": strategy_name, "remat": remat}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    model = build_model(cfg)
    dp = ("pod", "data") if multi_pod else ("data",)
    t0 = time.time()

    if shape.kind == "train":
        strat = make_strategy(strategy_name, mesh)
        tc = TrainConfig(remat=remat)
        step = make_train_step(model, tc, strat)
        state_specs = TS.state_specs(model, strat)
        state_abs = TS.abstract(model)
        batch_abs = SP.batch_specs(cfg, shape)
        bd = strat.batch_axes
        batch_specs = jax.tree_util.tree_map(
            lambda x: PS(bd, *([None] * (len(x.shape) - 1))), batch_abs)
        jitted = jax.jit(
            step,
            in_shardings=(_named(mesh, state_specs), _named(mesh, batch_specs)),
            out_shardings=(_named(mesh, state_specs), None),
            donate_argnums=(0,))
        with mesh:
            lowered = jitted.lower(state_abs, batch_abs)
            compiled = lowered.compile()
        tokens = shape.tokens
        mf = model_flops_per_token(cfg) * tokens * 3.0  # fwd+bwd = 3x fwd matmul flops... see note
        # NOTE: 6*N*D already counts fwd+bwd (2N fwd + 4N bwd per token);
        # so model_flops = 6*N per token exactly:
        mf = model_flops_per_token(cfg) * tokens
    elif shape.kind == "prefill":
        strat = make_strategy(strategy_name if strategy_name != "tp_serve"
                              else "dp_tp", mesh)
        pstep = make_prefill_step(model, strat)
        params_abs = model.abstract_params(jnp.bfloat16)
        p_specs = strat.param_specs(model)
        batch_abs = SP.batch_specs(cfg, shape)
        bd = strat.batch_axes
        batch_specs = jax.tree_util.tree_map(
            lambda x: PS(bd, *([None] * (len(x.shape) - 1))), batch_abs)
        jitted = jax.jit(pstep, in_shardings=(
            _named(mesh, p_specs), _named(mesh, batch_specs)))
        with mesh:
            lowered = jitted.lower(params_abs, batch_abs)
            compiled = lowered.compile()
        # fwd only: 2N of the 6N convention
        mf = model_flops_per_token(cfg) / 3.0 * shape.tokens
    else:  # decode
        params_bytes = 2 * model_flops_per_token(cfg) / 6.0
        wg = (params_bytes / mesh.shape["model"]) > _SERVE_WG_BYTES
        strat = make_strategy("tp_serve", mesh, weight_gathered=wg)
        rec["weight_gathered"] = bool(wg)
        sstep = make_serve_step(model, strat)
        params_abs, cache_abs, tok_abs = SP.decode_inputs(model, cfg, shape)
        p_specs = strat.param_specs(model)
        c_specs = strat.cache_specs(cache_abs, shape.global_batch)
        import numpy as _np
        dpn = int(_np.prod([mesh.shape[a] for a in dp]))
        tok_spec = PS(dp, None) if shape.global_batch % dpn == 0 else PS()
        jitted = jax.jit(
            sstep,
            in_shardings=(_named(mesh, p_specs), _named(mesh, c_specs),
                          NamedSharding(mesh, tok_spec)),
            out_shardings=(NamedSharding(mesh, tok_spec),
                           _named(mesh, c_specs)),
            donate_argnums=(1,))
        with mesh:
            lowered = jitted.lower(params_abs, cache_abs, tok_abs)
            compiled = lowered.compile()
        # one token per sequence; fwd-only flops
        mf = model_flops_per_token(cfg) / 3.0 * shape.global_batch
        # decode ideal: every weight byte + cache byte read once
        cache_bytes = sum(
            int(_np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree_util.tree_leaves(cache_abs))
        rec["min_bytes_global"] = params_bytes + cache_bytes

    compile_s = time.time() - t0
    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, list):  # older jax returns [dict]
        xla_cost = xla_cost[0]
    mem = _mem_summary(compiled)
    kib = RL.ideal_kernel_bytes(cfg, shape) if shape.kind != "decode" else 0.0
    terms = RL.analyze_compiled(compiled, chips, mf,
                                kernel_ideal_bytes_global=kib,
                                min_bytes_global=rec.get("min_bytes_global", 0.0))
    rec.update(status="ok", compile_s=round(compile_s, 1), memory=mem,
               xla_flops_per_device=float(xla_cost.get("flops", 0.0)),
               **terms)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run driver")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--strategy", default="dp_tp",
                    help="train/prefill strategy (decode always tp_serve)")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    archs = registry.ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = (list(SHAPES_BY_NAME) if args.shape == "all"
              else args.shape.split(","))
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                name = f"{arch}_{shape}_{'multi' if mp else 'single'}_{args.tag}.json"
                path = OUT_DIR / name
                if path.exists() and not args.force:
                    print(f"[skip existing] {name}", flush=True)
                    continue
                print(f"[dryrun] {arch} x {shape} x "
                      f"{'2x16x16' if mp else '16x16'} ({args.strategy})",
                      flush=True)
                try:
                    rec = run_cell(arch, shape, mp, args.strategy, args.remat)
                except Exception as e:  # record failures — they are bugs
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "strategy": args.strategy, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                rec["tag"] = args.tag
                path.write_text(json.dumps(rec, indent=2))
                status = rec.get("status")
                extra = (f" dominant={rec.get('dominant')} "
                         f"rf={rec.get('roofline_fraction', 0):.3f} "
                         f"compile={rec.get('compile_s')}s"
                         if status == "ok" else rec.get("reason") or rec.get("error", ""))
                print(f"  -> {status} {extra}", flush=True)
                results.append(rec)
    n_ok = sum(r.get("status") == "ok" for r in results)
    print(f"done: {n_ok} ok / {len(results)} attempted", flush=True)
    if any(r.get("status") == "error" for r in results):
        sys.exit(1)


if __name__ == "__main__":
    main()
