"""The paper's CNN (McMahan-style FL-MNIST CNN) in pure JAX.

conv5x5x32 -> maxpool2 -> conv5x5x64 -> maxpool2 -> fc512 -> fc10.

``loss_many`` runs R replicas' forward passes as one program with the
replica axis in the channel (lane) axis of every activation from the
input to the pool2 output: the convs are grouped (``feature_group_count
= R``), and bias, ReLU and the pools see (B, H, W, R*C), which is
lane-dense where ``jax.vmap(loss)`` would leave R as a leading axis and
pad each replica's 32 or 64 channels out to 128 lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.paper_cnn import PaperCnnConfig
from repro.models.params import ParamDef, init_params, param_count


class CNN:
    def __init__(self, cfg: PaperCnnConfig):
        self.cfg = cfg

    def defs(self) -> dict:
        c = self.cfg
        c1, c2 = c.channels
        k = c.kernel
        flat = (c.image_size // 4) ** 2 * c2
        return {
            "conv1_w": ParamDef((k, k, 1, c1), scale=0.1),
            "conv1_b": ParamDef((c1,), "zeros"),
            "conv2_w": ParamDef((k, k, c1, c2), scale=0.05),
            "conv2_b": ParamDef((c2,), "zeros"),
            "fc1_w": ParamDef((flat, c.hidden)),
            "fc1_b": ParamDef((c.hidden,), "zeros"),
            "fc2_w": ParamDef((c.hidden, c.num_classes)),
            "fc2_b": ParamDef((c.num_classes,), "zeros"),
        }

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        return init_params(self.defs(), key, dtype)

    def count_params(self) -> int:
        return param_count(self.defs())

    def forward(self, p: dict, images: jax.Array) -> jax.Array:
        """images: (B, 28, 28) -> logits (B, 10)."""
        x = images[..., None]                           # NHWC
        x = _conv(x, p["conv1_w"])
        x = jax.nn.relu(x + p["conv1_b"][None, None, None])
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        x = _conv(x, p["conv2_w"])
        x = jax.nn.relu(x + p["conv2_b"][None, None, None])
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ p["fc1_w"] + p["fc1_b"][None])
        return x @ p["fc2_w"] + p["fc2_b"][None]

    def loss(self, p: dict, images: jax.Array, labels: jax.Array):
        return _mean_xent(self.forward(p, images), labels)

    def forward_many(self, p: dict, images: jax.Array) -> jax.Array:
        """``forward`` of R replicas at once: ``p`` stacked (R, ...),
        images (R, B, 28, 28) -> logits (R, B, 10).

        The same ops in the same order as ``forward``; only where the
        replica axis lives differs. Replica r owns channels
        [r*C, (r+1)*C) of every activation up to the pool2 output."""
        r, b = images.shape[:2]
        x = jnp.moveaxis(images, 0, -1)                 # (B, 28, 28, R)
        x = _grouped_conv(x, p["conv1_w"])               # (B, 28, 28, R*32)
        x = jax.nn.relu(x + p["conv1_b"].reshape(-1)[None, None, None])
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        x = _grouped_conv(x, p["conv2_w"])               # (B, 14, 14, R*64)
        x = jax.nn.relu(x + p["conv2_b"].reshape(-1)[None, None, None])
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        # (B, 7, 7, R, 64) -> (R, B, 7*7*64): each replica's HWC flatten.
        x = x.reshape(b, *x.shape[1:3], r, -1)
        x = jnp.moveaxis(x, 3, 0).reshape(r, b, -1)
        x = jax.nn.relu(jnp.einsum("rbi,rih->rbh", x, p["fc1_w"])
                        + p["fc1_b"][:, None])
        return jnp.einsum("rbi,rih->rbh", x, p["fc2_w"]) + p["fc2_b"][:, None]

    def loss_many(self, p: dict, images: jax.Array, labels: jax.Array):
        """R replicas' losses as one program (``forward_many``).

        ``p`` stacked (R, ...), images (R, B, 28, 28), labels (R, B).
        Returns ``(total, per_replica)``: ``total`` is the sum over
        replicas of each replica's mean cross-entropy, so its gradient
        gives each replica its own gradient, as ``jax.vmap(grad(loss))``
        does; ``per_replica`` (R,) is the aux for
        ``value_and_grad(..., has_aux=True)``."""
        per = _mean_xent(self.forward_many(p, images), labels)
        return jnp.sum(per), per

    def accuracy(self, p: dict, images: jax.Array, labels: jax.Array):
        return jnp.mean(
            (jnp.argmax(self.forward(p, images), -1) == labels).astype(
                jnp.float32))


def _mean_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean cross-entropy over the batch (last) axis of ``labels``."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold, axis=-1)


def _conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``forward``'s SAME conv of one replica: NHWC by HWIO."""
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _split(x: jax.Array, r: int) -> jax.Array:
    """(B, H, W, R*C) -> (B, H, W, R, C): a view, replica-major."""
    return x.reshape(*x.shape[:3], r, x.shape[3] // r)


@jax.custom_vjp
def _grouped_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``_conv`` of R replicas carried in the channels of ``x``
    (B, H, W, R*I) with their stacked kernels ``w`` (R, k, k, I, O).
    Returns (B, H, W, R*O).

    ``vmap`` of the one-replica conv over the (R, I) split of the
    channels, which JAX's batching rule makes one conv with
    ``feature_group_count=R`` on the grouped kernel (k, k, I, R*O), with
    no transpose of ``x``. The gradients are ``vmap`` of the one-replica
    gradients, batched the same way: this is the formulation
    ``jax.vmap(grad(loss))`` gives. Differentiating the grouped conv
    itself would give the weight gradient as a ``batch_group_count=R``
    conv instead. The ``custom_vjp`` is kept for the CPU, where the
    tests and the CPU recipes train: there the burst runs at vmap's
    speed, while plain autodiff runs a 3-step R=40 burst of batch 4 in
    20.7 s against 3.3 s, and of batch 32 in 186 s against 83 s. On a
    TPU v5e the two forms compile to the same 5-D views and run alike:
    8.58 against 8.61 ms per SGD step at R=40, 40.76 against 40.74 at
    R=196 (batch 32)."""
    y = jax.vmap(_conv, in_axes=(3, 0), out_axes=3)(_split(x, w.shape[0]), w)
    return y.reshape(*y.shape[:3], -1)


def _grouped_conv_fwd(x, w):
    return _grouped_conv(x, w), (x, w)


def _grouped_conv_bwd(res, g):
    x, w = res
    r = w.shape[0]

    def one(xr, wr, gr):
        return jax.vjp(_conv, xr, wr)[1](gr)

    dx, dw = jax.vmap(one, in_axes=(3, 0, 3), out_axes=(3, 0))(
        _split(x, r), w, _split(g, r))
    return dx.reshape(x.shape), dw


_grouped_conv.defvjp(_grouped_conv_fwd, _grouped_conv_bwd)
