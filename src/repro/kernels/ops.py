"""Jit'd wrappers for the Pallas kernels.

On CPU (this container) `interpret=True` is selected automatically so the
kernels execute step-by-step in Python; on TPU the same call sites compile
to Mosaic. Wrappers pick hardware-aligned default block shapes and accept
pytrees where useful (``fedagg_tree``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.treeops import tree_combine
from repro.kernels.fedagg import fedagg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_wkv import rwkv6_wkv
from repro.kernels.selective_scan import selective_scan


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("block_p",))
def fedagg_op(stacked: jax.Array, weights: jax.Array,
              block_p: int | None = None) -> jax.Array:
    return fedagg(stacked, weights, block_p=block_p, interpret=_on_cpu())


def fedagg_tree(params_stacked, weights):
    """Weighted aggregation over a satellite-stacked pytree via the fused
    kernel: flatten -> one kernel pass -> unflatten."""
    leaves, treedef = jax.tree.flatten(params_stacked)
    s = leaves[0].shape[0]
    flat = jnp.concatenate(
        [l.reshape(s, -1).astype(jnp.float32) for l in leaves], axis=1)
    agg = fedagg_op(flat, jnp.asarray(weights, jnp.float32))
    out = []
    ofs = 0
    for l in leaves:
        n = int(np.prod(l.shape[1:]))
        out.append(agg[ofs:ofs + n].reshape(l.shape[1:]).astype(l.dtype))
        ofs += n
    return jax.tree.unflatten(treedef, out)


def pad_stacked_rows(params_stacked, weights, multiple: int):
    """Pad the leading (satellite) axis of a stacked tree + its weight
    vector up to the next multiple of ``multiple`` with zero rows and
    zero weights.

    The contract that makes satellite-axis sharding correct for ANY
    ``S``: a padded row is ``0.0 * 0.0`` through both fold backends
    (Pallas ``fedagg`` mul+sum and the einsum dot), so it contributes
    *exactly* zero to the aggregate — appending zero terms to an f32 sum
    leaves every partial bit-identical. Device counts that do not divide
    ``S`` therefore fold the same aggregate as the unpadded call. Safe
    inside jit (the pad amount is static).
    """
    if multiple < 1:
        raise ValueError(f"pad multiple must be >= 1, got {multiple}")
    leaves = jax.tree.leaves(params_stacked)
    s = leaves[0].shape[0]
    pad = (-s) % multiple
    if not pad:
        return params_stacked, jnp.asarray(weights)
    padded = jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]), params_stacked)
    w = jnp.concatenate(
        [jnp.asarray(weights), jnp.zeros(pad, jnp.asarray(weights).dtype)])
    return padded, w


def fold_stacked_tree(params_stacked, weights, use_pallas: bool | None = None,
                      pad_to: int | None = None):
    """The simulator's weighted model fold: Σ_s weights[s]·stacked[s].

    Backend dispatch for the round megastep (``repro.sim.executor``): on
    accelerators the fold streams the flattened model through the fused
    Pallas kernel (:func:`fedagg_tree` — one HBM pass, weights resident
    in VMEM); on CPU the per-leaf einsum reference
    (:func:`repro.core.treeops.tree_combine`) is both the fast path and
    the interpret-mode equivalence oracle (Pallas interpret mode is
    ~100x slower than the einsum and only exercised by the tests).
    Safe to call inside jit; ``use_pallas`` overrides the backend pick.

    ``pad_to`` pads the satellite axis to the next multiple with
    zero-weighted dead rows (:func:`pad_stacked_rows`) — the shard-ready
    form for device counts that do not divide ``S``; exact through both
    backends.
    """
    if pad_to is not None:
        params_stacked, weights = pad_stacked_rows(
            params_stacked, weights, pad_to)
    if use_pallas is None:
        use_pallas = not _on_cpu()
    if use_pallas:
        return fedagg_tree(params_stacked, weights)
    return tree_combine(params_stacked, weights)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q",
                                    "block_k"))
def flash_attention_op(q, k, v, causal: bool = True,
                       window: int | None = None,
                       block_q: int = 128, block_k: int = 128):
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=_on_cpu())


@functools.partial(jax.jit, static_argnames=("chunk", "block_d"))
def selective_scan_op(abar, bx, c, chunk: int = 64, block_d: int = 256):
    return selective_scan(abar, bx, c, chunk=chunk, block_d=block_d,
                          interpret=_on_cpu())


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_wkv_op(r, k, v, w, u, chunk: int = 64):
    return rwkv6_wkv(r, k, v, w, u, chunk=chunk, interpret=_on_cpu())
