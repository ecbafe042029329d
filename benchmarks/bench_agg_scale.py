"""Aggregation scaling: paper-faithful chain math vs fused weighted mean.

Measures wall time of Eq.-14 chain aggregation vs the closed-form
weighted sum (fedagg kernel path) on growing model sizes — the CPU
analogue of the collective-payload reduction measured in §Perf.

Emits CSV: n_params,chain_us,fused_us,speedup.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import chain_weights
from repro.launch.compile_cache import use_compile_cache


def run() -> list[tuple[int, float, float, float]]:
    rows = []
    s = 8  # satellites in one orbit
    sizes = np.random.default_rng(0).uniform(1, 10, s)
    lam = jnp.asarray(chain_weights(sizes, sizes.sum(), "paper"),
                      jnp.float32)
    for log_p in (14, 17, 20, 22):
        p = 1 << log_p
        stacked = jax.random.normal(jax.random.key(0), (s, p))

        @jax.jit
        def chain(x):
            acc = x[0]
            m_acc = sizes[0]
            for i in range(1, s):
                gamma = float(sizes[i] / sizes.sum())
                acc = (1 - gamma) * acc + gamma * x[i]
            return acc

        @jax.jit
        def fused(x):
            return jnp.einsum("s,sp->p", lam, x)

        for f in (chain, fused):
            jax.block_until_ready(f(stacked))  # fedlint: disable=FHL004 — warmup sync before timing
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(chain(stacked))  # fedlint: disable=FHL004 — microbench measures per-call latency by design
        t_chain = (time.perf_counter() - t0) / 10 * 1e6
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(fused(stacked))  # fedlint: disable=FHL004 — microbench measures per-call latency by design
        t_fused = (time.perf_counter() - t0) / 10 * 1e6
        rows.append((p, t_chain, t_fused, t_chain / t_fused))
    return rows


if __name__ == "__main__":
    use_compile_cache()
    print("n_params,chain_us,fused_us,speedup")
    for p, c, f, s in run():
        print(f"{p},{c:.0f},{f:.0f},{s:.2f}")
