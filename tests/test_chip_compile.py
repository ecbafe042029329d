"""Compile rehearsals for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax; it compiles for a topology that
is described rather than attached, and refuses what the chip would
refuse (VMEM overflow, HBM overflow). These tests compile the main
path's kernel, the Pallas ``fedagg`` fold, at the paper CNN's width and
at every replica count the cells use: S=40 is the paper 5x8 shell, 200
the per-chip shard of an 800-satellite constellation on four chips, 800
that constellation on one. The paper CNN's replica-stacked SGD burst is
compiled at the benchmark cells' replica counts, for its layout and its
temporaries.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and pytest
workers that each collect this file must see the same tests. Keep every
such compile in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_cnn import CONFIG as CNN_CONFIG
from repro.kernels import ops
from repro.kernels.fedagg import MAX_BLOCK_P, fedagg, pick_block_p
from repro.models import CNN
from repro.sim.trainer import LocalTrainer

P_CNN = 1_663_370          # paper CNN parameter count


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache off here.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("s", [1, 10, 40, 200, 800])
def test_fedagg_compiles_for_v5e(one_chip, s):
    x = jax.ShapeDtypeStruct((s, P_CNN), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((s,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b: fedagg(a, b, interpret=False)).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fold_in_scope_keeps_kernel_name(one_chip, monkeypatch):
    """The simulator's fold inside its ``fold`` scope, as the executor
    runs it at the paper's S=40: the custom call keeps the name that
    the benchmark's ``fold_roofline`` matches (``^%fedagg_op(\\.\\d+)?
    = ``) and carries the scope in its metadata."""
    # The rehearsal's backend is the CPU: steer the kernel's interpret
    # switch to what the chip takes.
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    tree = {"w": jax.ShapeDtypeStruct((40, P_CNN), jnp.float32,
                                      sharding=one_chip)}
    w = jax.ShapeDtypeStruct((40,), jnp.float32, sharding=one_chip)

    def fold(t, w):
        with jax.named_scope("fold"):
            return ops.fold_stacked_tree(t, w, use_pallas=True)

    text = jax.jit(fold).lower(tree, w).compile().as_text()
    call = re.search(r"^\s*%fedagg_op(\.\d+)? = .*custom-call\(.*$", text,
                     re.M)
    assert call is not None and "tpu_custom_call" in call.group(0)
    assert re.search(r'op_name="[^"]*fold/', call.group(0))


@pytest.mark.parametrize("s", [40, 32, 196, 200])
def test_cnn_burst_has_no_vmap_layout(one_chip, s):
    """The paper CNN's train burst, batch 32, 2 steps, at S=40 (fedhap's
    paper-5x8 round), 32 (FedSat's padded tick) and 196 and 200 (the
    per-chip shards of 784 and 800 satellites on four chips). With the
    replica axis in the channels, conv1's activations are (32, 28, 28,
    S*32): no rank-5 ``f32[S,32,28,28,32]`` buffer, the layout
    ``jax.vmap(multi_step)`` gives them, with 32 channels padded to 128
    lanes. The temporaries stay under 20 MB per replica: about 13 MB at
    S=40 and 15 MB at S=196, against 42 MB and 22 MB under vmap. XLA's
    grouped convs still make 5-D views of their own (conv2's input
    gradient, conv1's weight gradient); this guards neither."""
    model = CNN(CNN_CONFIG)
    tr = LocalTrainer(model)
    params = jax.eval_shape(model.init, jax.random.key(0))
    stacked = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
        (s,) + p.shape, p.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((s, 2, 32, 28, 28), jnp.float32,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((s, 2, 32), jnp.int32, sharding=one_chip)
    compiled = jax.jit(tr.multi_step_many).lower(stacked, x, y).compile()
    assert f"f32[{s},32,28,28,32]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6 * s


def test_block_p_follows_replica_count():
    widths = [pick_block_p(s) for s in (1, 40, 80, 200, 800)]
    assert widths == sorted(widths, reverse=True)
    assert widths[0] == MAX_BLOCK_P and widths[-1] == 1024
    assert all(w % 1024 == 0 for w in widths)
    with pytest.raises(ValueError, match="MiB VMEM budget"):
        pick_block_p(2048)


def test_auto_block_p_is_bitwise_equal_to_widest():
    """Each column's sum over S keeps its order whatever the tile width,
    so the S-dependent width folds exactly what a 16384 tile folds. At
    S=80 both fit, and they differ."""
    s = 80
    assert pick_block_p(s) < MAX_BLOCK_P
    rng = np.random.default_rng(0)
    p = 3 * MAX_BLOCK_P + 1000          # several tiles and a ragged tail
    x = jnp.asarray(rng.standard_normal((s, p)).astype(np.float32))
    w = jnp.asarray(rng.random(s).astype(np.float32))
    auto = fedagg(x, w, interpret=True)
    widest = fedagg(x, w, block_p=MAX_BLOCK_P, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(widest))
