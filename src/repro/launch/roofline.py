import os
if "--xla" not in str(os.environ.get("XLA_FLAGS", "")):
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

"""Roofline analysis from compiled dry-run artifacts.

XLA's HloCostAnalysis counts `while` bodies ONCE (verified empirically:
a 10-iteration scanned matmul reports 1 matmul), so whole-module numbers
under-count deep scanned stacks. This module therefore uses *per-component
differencing*: lower the model at 1 and 2 pattern-periods with every
inner loop (layer stack, attention q-chunks, ssm/wkv chunks) Python-
unrolled, take the difference as the per-period cost, and extrapolate:

    total = base + num_periods * per_period  (+ aggregation, for train)

The FedHAP aggregation round is compiled separately at full model size
(its ring hops are statically unrolled, so its collectives are exact).

Terms (TPU v5e): compute = flops/dev / 197e12, memory = bytes/dev /
819e9, collective = collective-bytes/dev / 50e9. cost_analysis numbers
are per-partition (per-device) under SPMD.
"""
import argparse
import dataclasses
import json
import pathlib

import jax

import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_configs
from repro.core.mesh_round import FedRoundConfig, build_round
from repro.launch.dryrun import parse_collective_bytes
from repro.launch.mesh import make_constellation_map, make_production_mesh
from repro.launch.specs import (
    _dp,
    _lead,
    decode_input_specs,
    prefill_input_specs,
    sanitize_specs,
    train_input_specs,
    use_window_for,
)
from repro.models.transformer import Transformer, cross_entropy_loss
from jax.sharding import NamedSharding, PartitionSpec as P

PEAK_FLOPS = 197e12    # bf16 / chip
HBM_BW = 819e9         # B/s / chip
LINK_BW = 50e9         # B/s / ICI link

_SUGGEST = {
    "compute": ("fuse the hot matmul chain into a Pallas kernel / raise "
                "arithmetic intensity (larger per-device tiles, less "
                "remat recompute)"),
    "memory": ("cut HBM traffic: bf16 aggregation buffers, fewer "
               "activation re-reads (fused blockwise attention), or a "
               "remat policy that trades recompute for reads"),
    "collective": ("replace the K-hop ring echo with the fused "
                   "closed-form round (one all-reduce), or overlap "
                   "aggregation collectives with local compute"),
}


def _extract(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total_bytes"]),
        "coll_detail": {k: v for k, v in coll.items()
                        if isinstance(v, dict) and v["count"]},
    }


def _variant(cfg, n_periods: int):
    pat = len(cfg.block_pattern)
    upd = dict(num_layers=n_periods * pat, remat=False)
    if cfg.is_encdec:
        upd["encoder_layers"] = n_periods
    # Unrolled inner loops must stay compile-tractable on the CPU host:
    # enlarge chunk sizes (fewer, bigger blocks — identical matmul math;
    # the associative-scan log-depth term shifts marginally).
    if cfg.mamba is not None and cfg.mamba.chunk < 1024:
        upd["mamba"] = dataclasses.replace(cfg.mamba, chunk=1024)
    if cfg.rwkv is not None and cfg.rwkv.chunk < 512:
        upd["rwkv"] = dataclasses.replace(cfg.rwkv, chunk=512)
    return dataclasses.replace(cfg, **upd)


def _lower_compute(cfg, shape, mesh, cmap):
    """Compute-only step (no aggregation) with all loops unrolled."""
    model = Transformer(cfg)
    multi_pod = "pod" in mesh.axis_names
    example = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16))
    trailing = sanitize_specs(example, model.specs(), mesh)

    if shape.mode == "train":
        lead = _lead(multi_pod)
        pspec = jax.tree.map(lambda s: P(lead, *tuple(s)), trailing,
                             is_leaf=lambda x: isinstance(x, P))
        params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
        specs = train_input_specs(cfg, shape, cmap)
        batch_sh = jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(lead, *([None] * (len(x.shape) - 1)))),
            specs["batch"])
        params_spec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((cmap.total_sats,) + x.shape,
                                           x.dtype), example)

        def loss_one(p, batch):
            aux_in = {k: batch[k] for k in ("frames", "patches")
                      if k in batch}
            logits, aux = model.forward(p, batch["tokens"], aux_in or None,
                                        unroll=True)
            labels = batch["labels"]
            if cfg.vision_patches:
                logits = logits[:, -labels.shape[1]:]
            return cross_entropy_loss(logits, labels) + aux

        def local_step(params_S, batch):
            loss, grads = jax.vmap(jax.value_and_grad(loss_one))(params_S,
                                                                 batch)
            return jax.tree.map(
                lambda p, g: p - 0.01 * g.astype(p.dtype), params_S,
                grads), loss.mean()

        jitted = jax.jit(local_step, in_shardings=(params_sh, batch_sh))
        return jitted.lower(params_spec, specs["batch"]).compile()

    params_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s), trailing)
    if shape.mode == "prefill":
        specs = prefill_input_specs(cfg, shape)
        dp = _dp(multi_pod, shape.global_batch, mesh)
        in_sh = jax.tree.map(
            lambda x: NamedSharding(mesh,
                                    P(dp, *([None] * (len(x.shape) - 1)))),
            specs)

        def prefill(params, inputs):
            aux = {k: v for k, v in inputs.items()
                   if k in ("frames", "patches")}
            logits, _ = model.forward(params, inputs["tokens"],
                                      aux or None, unroll=True)
            return logits[:, -1, :]

        return jax.jit(prefill, in_shardings=(params_sh, in_sh)).lower(
            example, specs).compile()

    # decode
    use_window = use_window_for(cfg, shape)
    long_ctx = (shape.name == "long_500k") and not use_window
    from repro.launch.specs import make_serve_step
    serve, params_sh2, cache_sh, tok_sh = make_serve_step(
        model, mesh, use_window, long_ctx)

    def serve_unrolled(params, cache, token):
        logits, new_cache = model.decode_step(params, cache, token,
                                              use_window=use_window,
                                              unroll=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_cache

    specs = decode_input_specs(cfg, shape, model, use_window)
    jitted = jax.jit(serve_unrolled, in_shardings=(
        params_sh2, cache_sh(shape.global_batch, specs["cache"]),
        tok_sh(shape.global_batch)))
    return jitted.lower(example, specs["cache"], specs["token"]).compile()


def _lower_round(cfg, mesh, cmap, round_kind, partial_mode="paper",
                 ship_echo=True):
    """Aggregation round alone, at FULL model size (hops are unrolled)."""
    model = Transformer(cfg)
    multi_pod = "pod" in mesh.axis_names
    example = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.bfloat16))
    trailing = sanitize_specs(example, model.specs(), mesh)
    rcfg = FedRoundConfig(cmap=cmap, partial_mode=partial_mode,
                          ship_global_echo=ship_echo)
    round_fn = build_round(mesh, rcfg, model.defs(), model_specs=trailing,
                           kind=round_kind)
    lead = _lead(multi_pod)
    pspec = jax.tree.map(lambda s: P(lead, *tuple(s)), trailing,
                         is_leaf=lambda x: isinstance(x, P))
    params_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
    params_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((cmap.total_sats,) + x.shape,
                                       x.dtype), example)
    sc = NamedSharding(mesh, P(lead))
    jitted = jax.jit(round_fn, in_shardings=(params_sh, sc, sc))
    return jitted.lower(
        params_spec,
        jax.ShapeDtypeStruct((cmap.total_sats,), jnp.float32),
        jax.ShapeDtypeStruct((cmap.total_sats,), jnp.bool_)).compile()


def roofline_one(arch: str, shape_name: str, multi_pod: bool = False,
                 round_kind: str = "fedhap", partial_mode: str = "paper",
                 ship_echo: bool = True,
                 overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cmap = make_constellation_map(multi_pod=multi_pod)
    chips = int(jax.device_count())
    n_periods = cfg.num_layers // len(cfg.block_pattern)

    with jax.set_mesh(mesh):
        c1 = _extract(_lower_compute(_variant(cfg, 1), shape, mesh, cmap))
        c2 = _extract(_lower_compute(_variant(cfg, 2), shape, mesh, cmap))
        per_period = {k: c2[k] - c1[k] for k in ("flops", "bytes",
                                                 "coll_bytes")}
        base = {k: c1[k] - per_period[k] for k in per_period}
        total = {k: max(0.0, base[k] + n_periods * per_period[k])
                 for k in per_period}
        agg = None
        if shape.mode == "train":
            agg = _extract(_lower_round(cfg, mesh, cmap, round_kind,
                                        partial_mode, ship_echo))
            for k in total:
                total[k] += agg[k]

    model = Transformer(cfg)
    n_active = model.active_param_count()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        model_flops = 2.0 * n_active * shape.global_batch
    model_flops_dev = model_flops / chips

    terms = {
        "compute_s": total["flops"] / PEAK_FLOPS,
        "memory_s": total["bytes"] / HBM_BW,
        "collective_s": total["coll_bytes"] / LINK_BW,
    }
    dominant = max(terms, key=lambda k: terms[k]).replace("_s", "")
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": shape.mode,
        "round_kind": round_kind if shape.mode == "train" else None,
        "partial_mode": partial_mode if shape.mode == "train" else None,
        "ship_echo": ship_echo if shape.mode == "train" else None,
        "chips": chips,
        "per_device": total,
        "per_period": per_period,
        "base": base,
        "aggregation": agg,
        "terms_s": terms,
        "dominant": dominant,
        "model_flops_per_device": model_flops_dev,
        "useful_flops_ratio": (model_flops_dev / total["flops"]
                               if total["flops"] else 0.0),
        "suggestion": _SUGGEST[dominant],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_configs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--round", dest="round_kind", default="fedhap",
                    choices=["fedhap", "fedhap_fused", "fedavg"])
    ap.add_argument("--partial-mode", default="paper")
    ap.add_argument("--no-echo", dest="ship_echo", action="store_false")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. attn_chunk_q=4096")
    ap.add_argument("--tag", default="",
                    help="artifact filename suffix for variants")
    ap.add_argument("--out", default="runs/roofline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = (int(v) if v.lstrip("-").isdigit()
                        else (v == "True" if v in ("True", "False")
                              else v))

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    combos = ([(a, s) for a in list_configs() for s in SHAPES]
              if args.all else [(args.arch, args.shape)])
    multi = args.mesh == "multi"
    for arch, shape in combos:
        suffix = "" if args.round_kind == "fedhap" else f"_{args.round_kind}"
        if not args.ship_echo:
            suffix += "_noecho"
        if args.tag:
            suffix += f"_{args.tag}"
        name = f"{arch}_{shape}_{args.mesh}{suffix}.json"
        path = outdir / name
        if args.skip_existing and path.exists():
            print(f"[skip] {name}")
            continue
        print(f"[roofline] {arch} x {shape} ({args.round_kind}) ...",
              flush=True)
        try:
            art = roofline_one(arch, shape, multi, args.round_kind,
                               args.partial_mode, args.ship_echo,
                               overrides=overrides or None)
            art["overrides"] = overrides
            path.write_text(json.dumps(art, indent=1))
            t = art["terms_s"]
            print(f"  compute={t['compute_s']:.4f}s "
                  f"memory={t['memory_s']:.4f}s "
                  f"collective={t['collective_s']:.4f}s "
                  f"dominant={art['dominant']} "
                  f"useful={art['useful_flops_ratio']:.2f}", flush=True)
        except Exception as e:
            import traceback
            print(f"  FAILED: {e}\n{traceback.format_exc()}", flush=True)


if __name__ == "__main__":
    main()
