"""The replica-stacked SGD burst (``LocalTrainer.multi_step_many``).

The CNN's burst carries the replica axis in the channels of its
activations (``CNN.loss_many``); it has to train each replica exactly as
``jax.vmap(multi_step)`` does. The MLP's ``loss_many`` vmaps its
``loss``, and its stacked burst is ``jax.vmap(multi_step)`` bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import CONFIG as CNN_CONFIG
from repro.configs.paper_mlp import CONFIG as MLP_CONFIG
from repro.models import CNN, MLP
from repro.sim.trainer import LocalTrainer

STEPS, BS = 3, 4
TOL = dict(atol=1e-6, rtol=1e-5)


def burst(model, r: int, seed: int = 0):
    """``r`` distinct replicas of ``model`` and their 3-step streams."""
    tr = LocalTrainer(model)
    rng = np.random.default_rng(seed + r)
    stacked = jax.tree.map(
        lambda p: jnp.asarray(np.asarray(p)[None] + 0.01 * rng.standard_normal(
            (r, *p.shape), dtype=np.float32)),
        model.init(jax.random.key(seed)))
    x = jnp.asarray(rng.random((r, STEPS, BS, 28, 28), dtype=np.float32))
    y = jnp.asarray(rng.integers(0, 10, (r, STEPS, BS)), dtype=jnp.int32)
    return tr, stacked, x, y


@pytest.mark.parametrize("r", [1, 3, 40])
def test_cnn_stacked_burst_matches_vmap(r):
    tr, stacked, x, y = burst(CNN(CNN_CONFIG), r)
    want = jax.jit(jax.vmap(tr.multi_step))(stacked, x, y)
    got = jax.jit(tr.multi_step_many)(stacked, x, y)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
    # The burst moved every replica, each its own way.
    w0, w1 = stacked["fc2_w"], got[0]["fc2_w"]
    assert float(jnp.min(jnp.max(jnp.abs(w1 - w0), axis=(1, 2)))) > 0


def test_cnn_loss_many_is_each_replicas_loss():
    tr, stacked, x, y = burst(CNN(CNN_CONFIG), 3)
    model = tr.model
    total, per = model.loss_many(stacked, x[:, 0], y[:, 0])
    each = jax.vmap(model.loss)(stacked, x[:, 0], y[:, 0])
    np.testing.assert_allclose(np.asarray(per), np.asarray(each), **TOL)
    np.testing.assert_allclose(float(total), float(jnp.sum(each)), **TOL)


@pytest.mark.parametrize("r", [1, 3, 40])
def test_mlp_burst_is_vmap_bit_for_bit(r):
    tr, stacked, x, y = burst(MLP(MLP_CONFIG), r)
    want = jax.jit(jax.vmap(tr.multi_step))(stacked, x, y)
    got = jax.jit(tr.multi_step_many)(stacked, x, y)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kind", ["cnn", "mlp"])
def test_train_one_is_the_one_replica_burst(kind):
    """``_train_one`` runs the stacked burst at R=1; it trains as the
    one-replica ``multi_step`` does (the MLP bit for bit)."""
    model = CNN(CNN_CONFIG) if kind == "cnn" else MLP(MLP_CONFIG)
    tr, stacked, x, y = burst(model, 1)
    one = jax.tree.map(lambda p: p[0], stacked)
    want = jax.jit(tr.multi_step)(one, x[0], y[0])
    got = tr._train_one(one, x[0], y[0])
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape
        if kind == "mlp":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
