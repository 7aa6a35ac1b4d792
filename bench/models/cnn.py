"""Plain reference of the paper's CIFAR-10 model (Sec. V, Fig. 3): six
3x3 convolutions with same padding (32, 32, 64, 64, 128, 128 channels),
each followed by batch-norm and ReLU, a 2x2 max-pool and dropout (rates
0.2, 0.3, 0.4) after each pair, and a dense 2048 -> 10 head with
softmax cross-entropy.  308,394 parameters: 307,498 in the convs' and
the head's weights and biases, 896 in the batch-norms' scales and
biases.

Departure from the usual batch-norm: it normalises by the statistics
of the batch it is given, in training and in eval alike, and keeps no
running statistics (nor does the model the program runs).  Eval over
the test set therefore normalises by the test set's own statistics.

A convolution is written as the sum over the nine taps of a matmul of
the shifted, zero-padded input with the tap's [cin, cout] weights.
Dropout draws its masks as the program does: the step's dropout key
splits into (next, mask key) before each pool's mask, and a unit is
kept with probability 1 - rate and scaled by 1 / (1 - rate).

`init` makes the weights (the harness hands them to the program and to
the reference alike); `apply` is the forward pass in the dtype it is
given; `forward_flops` counts the FLOPs of one sample, `conv_flops`
those of its convolutions, and `conv_round_flops` those the
convolutions of a round require."""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHANNELS = ((3, 32), (32, 32), (32, 64), (64, 64), (64, 128), (128, 128))
DROPOUT = (0.2, 0.3, 0.4)
SIDE = 32                 # input height and width
D_FC, D_OUT = 4 * 4 * 128, 10
BN_EPS = 1e-5


def init(key):
    """{"conv": [{"b", "bn_bias", "bn_scale", "w" [3, 3, cin, cout]}] * 6,
    "fc_b" [10], "fc_w" [2048, 10]}: conv weights He-normal
    (variance 2 / (9 cin)), head weights N(0, 1 / 2048), biases 0,
    batch-norm scales 1."""
    keys = jax.random.split(key, len(CHANNELS) + 1)
    conv = []
    for k, (cin, cout) in zip(keys[:-1], CHANNELS):
        std = jnp.sqrt(jnp.float32(2.0 / (9 * cin)))
        conv.append({
            "b": jnp.zeros((cout,), jnp.float32),
            "bn_bias": jnp.zeros((cout,), jnp.float32),
            "bn_scale": jnp.ones((cout,), jnp.float32),
            "w": jax.random.normal(k, (3, 3, cin, cout), jnp.float32) * std,
        })
    fc_w = jax.random.normal(keys[-1], (D_FC, D_OUT), jnp.float32) \
        / jnp.sqrt(jnp.float32(D_FC))
    return {"conv": conv, "fc_b": jnp.zeros((D_OUT,), jnp.float32),
            "fc_w": fc_w}


def conv3x3(x, w):
    """Same-padded 3x3 convolution, stride 1: x [B, H, W, cin],
    w [3, 3, cin, cout] -> [B, H, W, cout]."""
    H, W = x.shape[1], x.shape[2]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    out = 0
    for i in range(3):
        for j in range(3):
            out = out + xp[:, i:i + H, j:j + W, :] @ w[i, j]
    return out


def batch_norm(y, scale, bias):
    """Normalise each channel by the batch's mean and variance over
    (B, H, W), then scale and shift."""
    mu = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean((y - mu) ** 2, axis=(0, 1, 2))
    return (y - mu) / jnp.sqrt(var + BN_EPS) * scale + bias


def max_pool2(h):
    B, H, W, C = h.shape
    return jnp.max(h.reshape(B, H // 2, 2, W // 2, 2, C), axis=(2, 4))


def apply(params, x, *, train=False, rng=None):
    """x [B, 32, 32, 3] -> logits [B, 10]; dropout only where `train`
    and a key `rng` are given."""
    h = x
    for i, p in enumerate(params["conv"]):
        h = conv3x3(h, p["w"]) + p["b"]
        h = jnp.maximum(batch_norm(h, p["bn_scale"], p["bn_bias"]), 0)
        if i % 2 == 1:
            h = max_pool2(h)
            if train and rng is not None:
                rng, sub = jax.random.split(rng)
                rate = DROPOUT[i // 2]
                keep = jax.random.bernoulli(sub, 1 - rate, h.shape)
                h = jnp.where(keep, h / (1 - rate), 0)
    return h.reshape(h.shape[0], -1) @ params["fc_w"] + params["fc_b"]


def layer_flops() -> list:
    """FLOPs of each convolution for one sample: 2 per multiply-add,
    H * W * 9 * cin * cout multiply-adds at the layer's side (32, 32,
    16, 16, 8, 8)."""
    out, side = [], SIDE
    for i, (cin, cout) in enumerate(CHANNELS):
        out.append(2 * side * side * 9 * cin * cout)
        if i % 2 == 1:
            side //= 2
    return out


def conv_flops() -> int:
    """FLOPs of one sample's convolutions."""
    return sum(layer_flops())


def forward_flops() -> int:
    """FLOPs of one sample's forward pass: the convolutions and the
    2048 x 10 head."""
    return conv_flops() + 2 * D_FC * D_OUT


def conv_round_flops(cfg: dict, seeds: int) -> int:
    """FLOPs a round's convolutions require, all seeds: for every
    training sample (S * C * M * I * tau * batch) the forward pass and
    the gradients with respect to the weights and to the inputs, less
    the first layer's input gradient, which nothing needs; one forward
    pass over the test set per seed for the eval."""
    samples = (seeds * cfg["C"] * cfg["M"] * cfg["I"] * cfg["tau"]
               * cfg["batch"])
    train = (3 * conv_flops() - layer_flops()[0]) * samples
    return train + conv_flops() * cfg["n_test"] * seeds
