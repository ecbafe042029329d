"""Federated training driver (single-host; production launch uses the
same step functions under the multi-pod mesh via dryrun-verified specs).

Trains an assigned architecture (usually a reduced variant on CPU) with
FedHAP rounds over synthetic per-satellite token corpora:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --reduced --rounds 20 --sats 4 --seq 256 --batch-per-sat 2 \
      --round-kind fedhap_fused
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint
from repro.configs import get_config, list_configs
from repro.core.dissemination import ConstellationMeshMap
from repro.core.weights import mu_weights
from repro.core.fed_step import (
    FedTrainConfig,
    build_fed_train_step,
    stack_params,
)
from repro.core.mesh_round import FedRoundConfig
from repro.data.tokens import TokenTaskConfig, make_token_dataset
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import Transformer


def make_batches(cfg, n_sats: int, batch: int, seq: int, step: int,
                 vocab: int, skew: float = 0.3):
    """Per-satellite next-token batches from the synthetic chain corpus."""
    tok_cfg = TokenTaskConfig(vocab_size=vocab, client_skew=skew, seed=7)
    toks = np.stack([
        make_token_dataset(batch * (seq + 1), tok_cfg, client=s,
                           seed_offset=step)
        .reshape(batch, seq + 1)
        for s in range(n_sats)
    ])
    return {"tokens": jnp.asarray(toks[:, :, :-1]),
            "labels": jnp.asarray(toks[:, :, 1:])}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_configs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--sats", type=int, default=4)
    ap.add_argument("--orbits", type=int, default=2)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch-per-sat", type=int, default=2)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--round-kind", default="fedhap",
                    choices=["fedhap", "fedhap_fused", "fedavg"])
    ap.add_argument("--partial-mode", default="paper",
                    choices=["paper", "exact"])
    ap.add_argument("--visibility", type=float, default=0.5,
                    help="per-round probability a satellite sees its HAP")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = Transformer(cfg)
    n_sats = args.sats
    assert n_sats % args.orbits == 0
    cmap = ConstellationMeshMap(
        n_orbits=args.orbits, sats_per_orbit=n_sats // args.orbits,
        n_pods=1)

    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_sats, max(1, n_dev // n_sats))
                         if n_dev >= n_sats else (1, 1),
                         ("data", "model"))
    if mesh.shape["data"] != n_sats:
        # single-device fallback: satellites time-multiplex one device.
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        cmap_run = dataclasses.replace(cmap)  # logical map unchanged
        print(f"[train] single-device run; logical satellites={n_sats}")

    fed_cfg = FedTrainConfig(
        round_cfg=FedRoundConfig(cmap=cmap, partial_mode=args.partial_mode,
                                 ship_global_echo=False),
        round_kind=args.round_kind,
        local_steps=args.local_steps,
        learning_rate=args.lr,
    )

    params = model.init(jax.random.key(args.seed))
    params_S = stack_params(params, n_sats)
    sizes = jnp.ones((n_sats,), jnp.float32)
    rng = np.random.default_rng(args.seed)

    if mesh.shape["data"] == n_sats:
        with jax.set_mesh(mesh):
            step_fn = jax.jit(build_fed_train_step(model, fed_cfg, mesh))
    else:
        step_fn = jax.jit(_single_device_round(model, fed_cfg))

    print(f"[train] {cfg.name}: {model.count_params()/1e6:.1f}M params, "
          f"{n_sats} satellites, {args.round_kind}")
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        for rnd in range(args.rounds):
            batch = make_batches(cfg, n_sats, args.batch_per_sat, args.seq,
                                 rnd, cfg.vocab_size)
            visible = jnp.asarray(
                _ensure_coverage(rng, cmap, args.visibility))
            params_S, metrics = step_fn(params_S, batch, sizes, visible)
            loss = float(metrics["local_loss"])
            print(f"  round {rnd:4d}  loss {loss:.4f}  "
                  f"gate {float(metrics['gate']):.0f}  "
                  f"({time.perf_counter()-t0:.1f}s)", flush=True)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir,
                        jax.tree.map(lambda x: x[0], params_S),
                        args.rounds, {"arch": cfg.name})
        print(f"[train] checkpoint written to {args.ckpt_dir}")


def _ensure_coverage(rng, cmap: ConstellationMeshMap, p: float):
    """Random visibility with >=1 visible satellite per orbit (so rounds
    aggregate; gating still exercised via the mask)."""
    v = rng.random(cmap.total_sats) < p
    k = cmap.sats_per_orbit
    for l in range(cmap.n_orbits * cmap.n_pods):
        if not v[l * k:(l + 1) * k].any():
            v[l * k + rng.integers(k)] = True
    return v


def _single_device_round(model: Transformer, fed_cfg: FedTrainConfig):
    """Reference round for 1-device runs: vmapped local SGD + the exact
    same aggregation math via segment weights (numpy path)."""
    from repro.core.fed_step import satellite_loss
    import functools

    loss_fn = functools.partial(satellite_loss, model)
    cmap = fed_cfg.round_cfg.cmap

    def step(params_S, batch, sizes, visible):
        def one(p_S, _):
            loss, grads = jax.vmap(jax.value_and_grad(loss_fn))(p_S, batch)
            return jax.tree.map(
                lambda p, g: p - fed_cfg.learning_rate * g.astype(p.dtype),
                p_S, grads), loss.mean()

        params_S, losses = jax.lax.scan(one, params_S, None,
                                        length=fed_cfg.local_steps)
        # aggregation via closed-form per-satellite weights
        mu = _mu_weights(visible, sizes, cmap,
                         fed_cfg.round_cfg.partial_mode,
                         fed_cfg.round_cfg.orbit_weighting)
        glob = jax.tree.map(
            lambda x: jnp.einsum("s,s...->...", mu,
                                 x.astype(jnp.float32)).astype(x.dtype),
            params_S)
        new = jax.tree.map(
            lambda g, x: jnp.broadcast_to(g[None], x.shape), glob, params_S)
        return new, {"local_loss": losses[-1],
                     "gate": jnp.ones(()), "covered": jnp.zeros(()),
                     "upload_mass": jnp.zeros(())}

    return step


def _mu_weights(visible, sizes, cmap, partial_mode, orbit_weighting):
    """Per-satellite global weights for 1-device runs — the shared
    closed-form engine (`repro.core.weights`), jnp backend."""
    return mu_weights(visible, sizes.astype(jnp.float32),
                      cmap.sats_per_orbit, partial_mode, orbit_weighting,
                      xp=jnp)


if __name__ == "__main__":
    main()
