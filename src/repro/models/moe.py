"""Mixture-of-Experts with capacity-based sort dispatch.

TPU-friendly static-shape pipeline (MaxText-style, adapted):
  router -> top-k -> flatten assignments -> stable sort by expert ->
  per-expert capacity slots -> gather into (E, C, D) -> batched expert
  FFN einsum -> gather back + gate-weighted combine.

Experts shard over the `model` mesh axis (expert parallelism): under
GSPMD the (E, C, D) dispatch buffer is sharded on E, which lowers the
dispatch/combine into all-to-all-style collectives on the ICI.

A load-balance auxiliary loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.params import ParamDef


def moe_defs(cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    f = m.d_ff_expert
    return {
        "router": ParamDef((d, m.num_experts), scale=0.02, axes=(None, None)),
        "w_gate": ParamDef((m.num_experts, d, f), axes=("model", None, None)),
        "w_up": ParamDef((m.num_experts, d, f), axes=("model", None, None)),
        "w_down": ParamDef((m.num_experts, f, d), axes=("model", None, None)),
    }


def capacity(m: MoEConfig, num_tokens: int) -> int:
    c = int(m.capacity_factor * m.top_k * num_tokens / m.num_experts)
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def apply_moe(cfg: ArchConfig, p: dict, x: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).

    Two dispatch modes:
    - default: one global dispatch buffer (E, C, D). Under GSPMD the
      data-dependent scatter forces a full-buffer all-reduce (measured:
      2 x 68.7 GB per layer at qwen3-moe prefill_32k) — kept as the
      baseline for §Perf.
    - ``cfg.moe_dispatch_local``: tokens dispatch inside their own data
      shard (G = moe_dispatch_blocks token blocks, each with capacity
      C/G); the scatter is shard-local and only the expert *weights*
      move (all-gather over `model`), ~100x less collective payload when
      experts are small relative to the token stream.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    g = cfg.moe_dispatch_blocks
    if cfg.moe_dispatch_local and t % g == 0 and t // g >= m.top_k:
        try:
            from jax.sharding import PartitionSpec as P
            xg = jax.lax.with_sharding_constraint(
                xt.reshape(g, t // g, d), P("data", None, None))
        except Exception:
            xg = xt.reshape(g, t // g, d)
        yg, aux = jax.vmap(lambda xb: _moe_tokens(cfg, p, xb))(xg)
        return yg.reshape(b, s, d), aux.mean()
    y, aux = _moe_tokens(cfg, p, xt)
    return y.reshape(b, s, d), aux


def _moe_tokens(cfg: ArchConfig, p: dict, xt: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Capacity-based sort dispatch over a flat token block (T, D)."""
    m = cfg.moe
    t, d = xt.shape
    e, k = m.num_experts, m.top_k
    cap = capacity(m, t)

    logits = (xt @ p["router"]).astype(jnp.float32)       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.clip(
        gate_vals.sum(-1, keepdims=True), 1e-9)            # renormalize

    # ---- flatten assignments and sort by expert (stable).
    e_flat = gate_idx.reshape(-1)                          # (T*k,)
    t_flat = jnp.arange(t * k) // k                        # token of each slot
    g_flat = gate_vals.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    t_sorted = t_flat[order]
    g_sorted = g_flat[order]
    # Position of each assignment within its expert's group.
    counts = jnp.bincount(e_flat, length=e)                # (E,)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[e_sorted]
    keep = pos_in_e < cap                                  # capacity drop
    slot = e_sorted * cap + jnp.minimum(pos_in_e, cap - 1)  # (T*k,)

    # ---- dispatch: (E*C, D).
    disp = jnp.zeros((e * cap, d), xt.dtype)
    disp = disp.at[slot].set(
        jnp.where(keep[:, None], xt[t_sorted], 0.0), mode="drop"
    )
    disp = disp.reshape(e, cap, d)
    if cfg.moe_ep_constraint:
        # Expert-parallel layout hint: keep dispatch/expert-output buffers
        # sharded on the expert axis so GSPMD lowers dispatch/combine into
        # all-to-all-style exchanges instead of all-gathering tokens.
        try:
            from jax.sharding import PartitionSpec as P
            disp = jax.lax.with_sharding_constraint(
                disp, P("model", None, None))
        except Exception:
            pass  # no mesh in context (CPU unit tests)

    # ---- expert FFN (batched einsum over experts; E shards over `model`).
    h = jnp.einsum("ecd,edf->ecf", disp, p["w_up"])
    gte = jnp.einsum("ecd,edf->ecf", disp, p["w_gate"])
    h = jax.nn.silu(gte) * h
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])       # (E, C, D)
    if cfg.moe_ep_constraint:
        try:
            from jax.sharding import PartitionSpec as P
            out = jax.lax.with_sharding_constraint(
                out, P("model", None, None))
        except Exception:
            pass

    # ---- combine: gather each kept assignment's output, gate-weight, sum.
    out_flat = out.reshape(e * cap, d)[slot]               # (T*k, D)
    contrib = jnp.where(keep[:, None], out_flat * g_sorted[:, None], 0.0)
    y = jnp.zeros((t, d), xt.dtype).at[t_sorted].add(
        contrib.astype(xt.dtype), mode="drop")

    # ---- Switch-style load-balance loss.
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs) * m.aux_loss_coef
    return y, aux
