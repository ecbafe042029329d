"""Kernel microbenchmarks: interpret-mode correctness + wall time of the
jnp reference path (the CPU-measurable proxy; TPU timing needs hardware).

Emits CSV: name,us_per_call,max_abs_err_vs_ref.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.launch.compile_cache import use_compile_cache


def _time(f, *args, iters=5):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))  # fedlint: disable=FHL004 — microbench measures per-call latency by design
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> list[tuple[str, float, float]]:
    rows = []
    k = jax.random.key

    x = jax.random.normal(k(0), (16, 1 << 18))
    w = jax.random.uniform(k(1), (16,))
    err = float(jnp.max(jnp.abs(
        ops.fedagg_op(x[:, :4096], w, block_p=1024)
        - ref.fedagg_ref(x[:, :4096], w))))
    us = _time(jax.jit(ref.fedagg_ref), x, w)
    rows.append(("fedagg_16x256k_ref", us, err))

    q = jax.random.normal(k(2), (1, 8, 512, 64))
    kk = jax.random.normal(k(3), (1, 2, 512, 64))
    v = jax.random.normal(k(4), (1, 2, 512, 64))
    err = float(jnp.max(jnp.abs(
        ops.flash_attention_op(q[:, :, :64], kk[:, :, :64], v[:, :, :64],
                               block_q=32, block_k=32)
        - ref.flash_attention_ref(q[:, :, :64], kk[:, :, :64],
                                  v[:, :, :64]))))
    us = _time(jax.jit(ref.flash_attention_ref), q, kk, v)
    rows.append(("flash_attn_512_gqa_ref", us, err))

    abar = jax.random.uniform(k(5), (2, 256, 64, 16), minval=0.5,
                              maxval=0.99)
    bx = jax.random.normal(k(6), (2, 256, 64, 16))
    c = jax.random.normal(k(7), (2, 256, 16))
    err = float(jnp.max(jnp.abs(
        ops.selective_scan_op(abar[:, :64], bx[:, :64], c[:, :64],
                              chunk=16, block_d=16)
        - ref.selective_scan_ref(abar[:, :64], bx[:, :64], c[:, :64]))))
    us = _time(jax.jit(ref.selective_scan_ref), abar, bx, c)
    rows.append(("selective_scan_256_ref", us, err))

    r = jax.random.normal(k(8), (1, 4, 256, 64))
    kw = jax.random.normal(k(9), (1, 4, 256, 64))
    vw = jax.random.normal(k(10), (1, 4, 256, 64))
    ww = jax.random.uniform(k(11), (1, 4, 256, 64), minval=0.9,
                            maxval=0.999)
    u = jax.random.normal(k(12), (4, 64))
    err = float(jnp.max(jnp.abs(
        ops.rwkv6_wkv_op(r[:, :, :32], kw[:, :, :32], vw[:, :, :32],
                         ww[:, :, :32], u, chunk=16)
        - ref.rwkv6_wkv_ref(r[:, :, :32], kw[:, :, :32], vw[:, :, :32],
                            ww[:, :, :32], u))))
    us = _time(jax.jit(ref.rwkv6_wkv_ref), r, kw, vw, ww, u)
    rows.append(("rwkv6_wkv_256_ref", us, err))
    return rows


if __name__ == "__main__":
    use_compile_cache()
    for name, us, err in run():
        print(f"{name},{us:.1f},{err:.2e}")
