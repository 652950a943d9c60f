"""Plain reference of the paper's stand-in classifier: an MLP
``input_dim → hidden × (depth − 1) → classes`` with ReLU, trained on
weighted cross-entropy.  Straight ``jax.numpy``; nothing of the program.

Weights come from the row seed the way the configuration documents them:
He-normal matrices (std √(2/fan_in)), zero biases, one key per layer split
from ``jax.random.key(seed)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_dims(cfg: dict):
    return ([int(cfg["input_dim"])] + [int(cfg["hidden"])]
            * (int(cfg["depth"]) - 1) + [int(cfg["classes"])])


def init(cfg: dict, seed: int):
    dims = layer_dims(cfg)
    keys = jax.random.split(jax.random.key(seed), len(dims) - 1)
    return [{"w": jax.random.normal(k, (i, o), jnp.float32)
             * jnp.sqrt(2.0 / i),
             "b": jnp.zeros((o,), jnp.float32)}
            for k, i, o in zip(keys, dims[:-1], dims[1:])]


def train_inputs(cfg: dict, data):
    """Per-example arrays a training batch gathers: (features, labels)."""
    return jnp.asarray(data.x), jnp.asarray(data.y)


test_inputs = train_inputs


def logits(params, x, prec):
    for i, layer in enumerate(params):
        x = jnp.matmul(x, layer["w"], precision=prec) + layer["b"]
        if i < len(params) - 1:
            x = jnp.maximum(x, 0)
    return x


def nll_sums(params, batch, w, cfg, prec):
    """(Σ w·nll, Σ w) of a batch: the two sums that blocks of a batch add
    up to, before ``weighted_mean`` divides them once."""
    x, y = batch
    z = logits(params, x, prec)
    nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, y[:, None], axis=1)[:, 0]
    return jnp.sum(nll * w), jnp.sum(w)


def weighted_mean(s, n):
    """Σ w·nll / max(Σ w, 1e-9) — eq. (1)'s example weighting."""
    return s / jnp.maximum(n, 1e-9)


def hits(params, batch, cfg, prec):
    """(correct top-1 predictions, predictions made) of a batch."""
    x, y = batch
    right = (jnp.argmax(logits(params, x, prec), -1) == y).astype(
        jnp.float32)
    return jnp.sum(right), right.size


def logits_per_example(cfg: dict) -> int:
    """Logits one example makes: the reference's blocking rules size a
    pass by them."""
    return int(cfg["classes"])


def train_flops_per_example(cfg: dict) -> float:
    """Multiply-adds of one example's forward and backward pass, ×2.
    Forward: every layer's matmul; backward: every layer's weight
    gradient, and the input gradient of every layer but the first (the
    data needs none).  Bias adds and activations are left out."""
    dims = layer_dims(cfg)
    macs = [i * o for i, o in zip(dims[:-1], dims[1:])]
    return 2.0 * (sum(macs) + sum(macs) + sum(macs[1:]))
