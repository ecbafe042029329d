"""The paper's MLP (2-hidden-layer perceptron, McMahan's 2NN) in JAX."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.paper_mlp import PaperMlpConfig
from repro.models.params import ParamDef, init_params, param_count


class MLP:
    def __init__(self, cfg: PaperMlpConfig):
        self.cfg = cfg

    def defs(self) -> dict:
        c = self.cfg
        d: dict = {}
        dims = (c.input_dim,) + c.hidden + (c.num_classes,)
        for i, (a, b) in enumerate(zip(dims, dims[1:])):
            d[f"w{i}"] = ParamDef((a, b))
            d[f"b{i}"] = ParamDef((b,), "zeros")
        return d

    def init(self, key: jax.Array, dtype=jnp.float32) -> dict:
        return init_params(self.defs(), key, dtype)

    def count_params(self) -> int:
        return param_count(self.defs())

    def forward(self, p: dict, images: jax.Array) -> jax.Array:
        x = images.reshape(images.shape[0], -1)
        n = len(self.cfg.hidden)
        for i in range(n):
            x = jax.nn.relu(x @ p[f"w{i}"] + p[f"b{i}"][None])
        return x @ p[f"w{n}"] + p[f"b{n}"][None]

    def loss(self, p: dict, images: jax.Array, labels: jax.Array):
        logits = self.forward(p, images)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    def loss_many(self, p: dict, images: jax.Array, labels: jax.Array):
        """R replicas' losses, ``p`` stacked (R, ...): ``(sum, (R,))``,
        as ``CNN.loss_many``; here the replica axis is vmap's."""
        per = jax.vmap(self.loss)(p, images, labels)
        return jnp.sum(per), per

    def accuracy(self, p: dict, images: jax.Array, labels: jax.Array):
        return jnp.mean(
            (jnp.argmax(self.forward(p, images), -1) == labels).astype(
                jnp.float32))
