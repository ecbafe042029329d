"""Fused weighted multi-replica aggregation kernel (FedHAP hot loop).

Computes out[p] = sum_s weights[s] * stacked[s, p] over a flat parameter
vector — the inner operation of every Eq. 14 fold and the Eq. 16 HAP
combine. On TPU the whole model (GBs) streams HBM->VMEM once in
hardware-aligned tiles while the (tiny) weight vector stays resident; the
fusion avoids S separate scale+add passes over HBM.

Tiling: grid over the parameter axis; each step loads an (S, BLOCK_P)
tile into VMEM, reduces over S on the VPU, writes (BLOCK_P,) out. The
tile width is picked from S (:func:`pick_block_p`) so the tile fits the
chip's scoped VMEM at every replica count; each column's sum over S is
independent of the width, so the output does not depend on it.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


MAX_BLOCK_P = 16_384
BLOCK_P_QUANTUM = 1_024
# TPU v5e's default scoped VMEM limit for one Mosaic kernel is 16 MiB;
# 2 MiB of it is left to the compiler's own scratch.
VMEM_BUDGET_BYTES = 14 * 2**20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def pick_block_p(s: int, itemsize: int = 4) -> int:
    """Widest tile (a multiple of 1024, at most 16384) whose VMEM
    footprint at ``s`` replicas fits the budget: the double-buffered
    (s, block_p) input tile, the kernel's f32 (s, block_p) product
    temporary, the double-buffered output row and the resident weights.
    Rows pad to the 8-sublane tile. Raises ``ValueError`` when even a
    1024-wide tile does not fit."""
    rows = _round_up(s, 8)
    per_col = rows * (2 * itemsize + 4) + 2 * itemsize
    fixed = 2 * rows * 128 * 4
    block_p = min(MAX_BLOCK_P,
                  (VMEM_BUDGET_BYTES - fixed) // per_col // BLOCK_P_QUANTUM
                  * BLOCK_P_QUANTUM)
    if block_p < BLOCK_P_QUANTUM:
        raise ValueError(
            f"fedagg: {s} replicas do not fit a {BLOCK_P_QUANTUM}-wide "
            f"tile in the {VMEM_BUDGET_BYTES / 2**20:.0f} MiB VMEM budget "
            f"({rows * BLOCK_P_QUANTUM * (2 * itemsize + 4) + fixed} B "
            f"needed); fold fewer replicas per call")
    return block_p


def _fedagg_kernel(w_ref, x_ref, o_ref):
    """w: (S, 1) VMEM; x: (S, BLOCK_P) VMEM tile; o: (BLOCK_P,)."""
    x = x_ref[...].astype(jnp.float32)          # (S, BP)
    w = w_ref[...].astype(jnp.float32)          # (S, 1)
    o_ref[...] = jnp.sum(x * w, axis=0).astype(o_ref.dtype)


def fedagg(
    stacked: jax.Array,      # (S, P) flat replicas
    weights: jax.Array,      # (S,)
    block_p: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Weighted sum over the replica axis; returns (P,). ``block_p``
    defaults to :func:`pick_block_p` of the replica count."""
    s, p = stacked.shape
    if block_p is None:
        block_p = pick_block_p(s, stacked.dtype.itemsize)
    block_p = min(block_p, p)
    pad = (-p) % block_p
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    grid = ((p + pad) // block_p,)
    out = pl.pallas_call(
        _fedagg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s, 1), lambda i: (0, 0)),       # weights resident
            pl.BlockSpec((s, block_p), lambda i: (0, i)),  # stream tiles
        ],
        out_specs=pl.BlockSpec((block_p,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(((p + pad),), stacked.dtype),
        interpret=interpret,
    )(weights[:, None], stacked)
    return out[:p]
