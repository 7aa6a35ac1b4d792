"""The paper's §V experiment models.

- MNIST: single-layer network, 784 -> 10 (2N = 7850 params incl. bias).
- CIFAR-10: CNN with conv pairs 32/64/128 (3x3, same padding) + BN + ReLU,
  2x2 max-pool + dropout after each pair, FC softmax head (2N = 308,394:
  307,498 conv and FC weights and biases plus 896 batch-norm scales and
  biases).

Pure JAX init/apply in the same Px convention as the big models.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.nn.core import Px


# --- MNIST single-layer -------------------------------------------------------

def mnist_init(key):
    kw, = jax.random.split(key, 1)
    w = jax.random.normal(kw, (784, 10), jnp.float32) / math.sqrt(784.0)
    return {
        "w": Px(w, ("p_embed", "vocab")),
        "b": Px(jnp.zeros((10,), jnp.float32), ("vocab",)),
    }


def mnist_apply(params, x, *, train: bool = False, rng=None):
    """x: [B, 784] -> logits [B, 10]."""
    return x @ params["w"] + params["b"]


# --- CIFAR-10 CNN -------------------------------------------------------------

_CHANNELS = [(3, 32), (32, 32), (32, 64), (64, 64), (64, 128), (128, 128)]
_DROPOUT = [0.2, 0.3, 0.4]


def _conv_init(key, cin, cout):
    k1, = jax.random.split(key, 1)
    fan_in = 3 * 3 * cin
    return {
        "w": Px(jax.random.normal(k1, (3, 3, cin, cout), jnp.float32)
                * math.sqrt(2.0 / fan_in), (None, None, None, None)),
        "b": Px(jnp.zeros((cout,), jnp.float32), (None,)),
        # batch-norm (we fold scale/bias; running stats updated outside jit
        # is unnecessary for the paper's experiments -> batch statistics)
        "bn_scale": Px(jnp.ones((cout,), jnp.float32), (None,)),
        "bn_bias": Px(jnp.zeros((cout,), jnp.float32), (None,)),
    }


def cifar_init(key):
    keys = jax.random.split(key, len(_CHANNELS) + 1)
    p: Dict = {"conv": [_conv_init(k, ci, co)
                        for k, (ci, co) in zip(keys[:-1], _CHANNELS)]}
    # after three 2x2 pools: 32 -> 16 -> 8 -> 4, channels 128
    d_fc = 4 * 4 * 128
    p["fc_w"] = Px(jax.random.normal(keys[-1], (d_fc, 10), jnp.float32)
                   / math.sqrt(d_fc), (None, None))
    p["fc_b"] = Px(jnp.zeros((10,), jnp.float32), (None,))
    return p


def _conv_bn_relu(p, x):
    # the convolution and its bias add carry the `cnn.conv` scope (their
    # backward ops inherit it), so a trace can tell the convs' time
    with jax.named_scope("cnn.conv"):
        y = jax.lax.conv_general_dilated(
            x, p["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
    mu = y.mean(axis=(0, 1, 2))
    var = y.var(axis=(0, 1, 2))
    y = (y - mu) * jax.lax.rsqrt(var + 1e-5)
    y = y * p["bn_scale"] + p["bn_bias"]
    return jax.nn.relu(y)


def cifar_apply(params, x, *, train: bool = False, rng=None):
    """x: [B, 32, 32, 3] -> logits [B, 10]."""
    h = x
    for i, cp in enumerate(params["conv"]):
        h = _conv_bn_relu(cp, h)
        if i % 2 == 1:
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
            if train and rng is not None:
                rng, sub = jax.random.split(rng)
                rate = _DROPOUT[i // 2]
                keep = jax.random.bernoulli(sub, 1 - rate, h.shape)
                h = jnp.where(keep, h / (1 - rate), 0.0)
    h = h.reshape(h.shape[0], -1)
    return h @ params["fc_w"] + params["fc_b"]


def n_params(tree) -> int:
    vals = jax.tree.leaves(jax.tree.map(
        lambda p: p.value if isinstance(p, Px) else p, tree,
        is_leaf=lambda v: isinstance(v, Px)))
    return sum(int(v.size) for v in vals)
