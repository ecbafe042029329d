import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Test hook: a smaller forced device count may be requested via env var —
# must happen before jax first initializes (device count locks at init).
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh).

For each combination this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the right step (train_step / prefill_step / serve_step)
     against ShapeDtypeStruct inputs (no allocation),
  3. compiles, prints memory_analysis() and cost_analysis(),
  4. parses the optimized HLO for collective ops and their byte volumes,
  5. writes a JSON artifact to runs/dryrun/ for the roofline stage.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
      --mesh single [--round fedhap|fedhap_fused|fedavg] [--out runs/dryrun]
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import pathlib
import re
import time
import traceback

import jax

import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_configs
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (
    decode_input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    prefill_input_specs,
    train_input_specs,
    use_window_for,
)
from repro.models.transformer import Transformer

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in optimized HLO.

    Methodology note (EXPERIMENTS.md §Roofline): output bytes are the
    payload proxy; ops inside `while` bodies are counted once — the
    roofline stage multiplies per-component numbers by trip counts
    instead of trusting whole-module statics.
    """
    out: dict[str, dict] = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        # LHS shapes can be tuples containing /*index=N*/ comments, so
        # capture everything between '=' and the op-name token.
        m = re.search(
            r"=\s*(.*?)\s*"
            r"\b(all-reduce-start|all-reduce|all-gather-start|all-gather|"
            r"reduce-scatter|all-to-all|collective-permute-start|"
            r"collective-permute)\(", line)
        if not m:
            continue
        shapes_str, op = m.group(1), m.group(2)
        op = op.replace("-start", "")
        total = 0
        for dt, dims in shape_re.findall(shapes_str):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[op]["count"] += 1
        out[op]["bytes"] += total
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:            # pragma: no cover - backend specific
        return {"error": str(e)}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    return {k: int(getattr(ma, k)) for k in keys if hasattr(ma, k)}


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              round_kind: str = "fedhap", partial_mode: str = "paper",
              local_steps: int = 1, keep_hlo: bool = False) -> dict:
    """Lower+compile one combination; returns the artifact dict."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = Transformer(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()

    with jax.set_mesh(mesh):
        if shape.mode == "train":
            step, params_sh, shardings_for, cmap = make_train_step(
                model, mesh, round_kind=round_kind,
                partial_mode=partial_mode, local_steps=local_steps)
            specs = train_input_specs(cfg, shape, cmap)
            in_sh = shardings_for(specs)
            params_spec = jax.eval_shape(
                lambda: model.init(jax.random.key(0), jnp.bfloat16))
            params_spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    (cmap.total_sats,) + x.shape, x.dtype), params_spec)
            jitted = jax.jit(step, in_shardings=(params_sh, in_sh["batch"],
                                                 in_sh["sizes"],
                                                 in_sh["visible"]))
            lowered = jitted.lower(params_spec, specs["batch"],
                                   specs["sizes"], specs["visible"])
        elif shape.mode == "prefill":
            prefill, params_sh, shardings_for = make_prefill_step(model,
                                                                  mesh)
            specs = prefill_input_specs(cfg, shape)
            in_sh = shardings_for(specs, shape.global_batch)
            params_spec = jax.eval_shape(
                lambda: model.init(jax.random.key(0), jnp.bfloat16))
            jitted = jax.jit(prefill, in_shardings=(params_sh, in_sh))
            lowered = jitted.lower(params_spec, specs)
        else:  # decode
            use_window = use_window_for(cfg, shape)
            long_ctx = (shape.name == "long_500k") and not use_window
            serve, params_sh, cache_sh, tok_sh = make_serve_step(
                model, mesh, use_window, long_ctx)
            specs = decode_input_specs(cfg, shape, model, use_window)
            params_spec = jax.eval_shape(
                lambda: model.init(jax.random.key(0), jnp.bfloat16))
            jitted = jax.jit(serve, in_shardings=(
                params_sh, cache_sh(shape.global_batch, specs["cache"]),
                tok_sh(shape.global_batch)))
            lowered = jitted.lower(params_spec, specs["cache"],
                                   specs["token"])

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = _memory_dict(compiled)
    cost_raw = compiled.cost_analysis() or {}
    cost = {k: float(v) for k, v in cost_raw.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "transcendentals",
             "utilization operand 0 {}", "optimal_seconds")}
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)

    artifact = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": shape.mode,
        "round_kind": round_kind if shape.mode == "train" else None,
        "partial_mode": partial_mode if shape.mode == "train" else None,
        "devices": int(jax.device_count()),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "cost_analysis": cost,
        "collectives": coll,
        "param_count": model.count_params(),
        "active_param_count": model.active_param_count(),
        "hlo_lines": hlo.count("\n"),
    }
    if keep_hlo:
        artifact["hlo_text"] = hlo
    return artifact


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--round", dest="round_kind", default="fedhap",
                    choices=["fedhap", "fedhap_fused", "fedavg"])
    ap.add_argument("--partial-mode", default="paper",
                    choices=["paper", "exact"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) for the given mesh")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    combos = []
    if args.all:
        for arch in list_configs():
            for shape in SHAPES:
                combos.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos.append((args.arch, args.shape))

    failures = []
    for arch, shape in combos:
        for multi_pod in meshes:
            mesh_tag = "multi" if multi_pod else "single"
            suffix = ("" if args.round_kind == "fedhap"
                      else f"_{args.round_kind}")
            name = f"{arch}_{shape}_{mesh_tag}{suffix}.json"
            path = outdir / name
            if args.skip_existing and path.exists():
                print(f"[skip] {name}")
                continue
            print(f"[dryrun] {arch} x {shape} x {mesh_tag} "
                  f"({args.round_kind}) ...", flush=True)
            try:
                art = lower_one(arch, shape, multi_pod,
                                round_kind=args.round_kind,
                                partial_mode=args.partial_mode)
                path.write_text(json.dumps(art, indent=1))
                print(f"  ok: compile={art['compile_s']}s "
                      f"flops={art['cost_analysis'].get('flops', 0):.3e} "
                      f"coll={art['collectives']['total_bytes']:.3e}B "
                      f"mem={art['memory_analysis']}", flush=True)
                print(f"  memory_analysis: {art['memory_analysis']}")
                print(f"  cost_analysis: {art['cost_analysis']}")
            except Exception as e:
                failures.append((arch, shape, mesh_tag, repr(e)))
                print(f"  FAILED: {e}\n{traceback.format_exc()}",
                      flush=True)
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
