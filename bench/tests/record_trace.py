#!/usr/bin/env python3
"""Record the small chip trace that ``test_tracereduce.py`` reads.

    python bench/tests/record_trace.py bench/tests/data/small_trace

On one TPU: two folds of 8 stacked replicas of the paper CNN through the
simulator's fold (the Pallas ``fedagg`` kernel on the chip) and a
matrix product, inside one ``bench.episode`` host span, with a host
sleep of 50 ms between them that leaves the device idle. Writes the
profiler's ``.xplane.pb`` under the given directory, and the known
facts of the recording beside it in ``facts.json``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_cnn import CONFIG  # noqa: E402
from repro.kernels.ops import fold_stacked_tree  # noqa: E402
from repro.models import CNN  # noqa: E402

ROWS = 8
SLEEP_S = 0.05


def main() -> int:
    out = pathlib.Path(sys.argv[1])
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    base = CNN(CONFIG).init(jax.random.key(0))
    stacked = jax.tree.map(lambda x: jnp.stack([x] * ROWS), base)
    w = jnp.full((ROWS,), 1.0 / ROWS, jnp.float32)
    fold = jax.jit(fold_stacked_tree)
    mm = jax.jit(lambda a: a @ a)
    a = jnp.ones((2048, 2048), jnp.float32)
    jax.block_until_ready((fold(stacked, w), mm(a)))
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.episode"):
        jax.block_until_ready(fold(stacked, w))
        time.sleep(SLEEP_S)
        jax.block_until_ready(mm(a))
        jax.block_until_ready(fold(stacked, w))
    jax.profiler.stop_trace()
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(base))
    (out / "facts.json").write_text(json.dumps({
        "rows": ROWS, "params": n_params, "folds": 2, "sleep_s": SLEEP_S,
        "device_kind": jax.devices()[0].device_kind}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
