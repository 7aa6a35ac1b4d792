"""Plain reference of the paper's MNIST model (Sec. V): one dense layer
784 -> 10 with bias, 7,850 parameters, softmax cross-entropy.

`init` makes the weights (the harness hands them to the program and to
the reference alike); `apply` is the forward pass in the dtype it is
given; `forward_flops` counts the FLOPs of one sample."""
from __future__ import annotations

import jax
import jax.numpy as jnp

D_IN, D_OUT = 784, 10


def init(key):
    """{"b": [10], "w": [784, 10]}: w ~ N(0, 1/784), b = 0."""
    w = jax.random.normal(key, (D_IN, D_OUT), jnp.float32) / jnp.sqrt(
        jnp.float32(D_IN))
    return {"b": jnp.zeros((D_OUT,), jnp.float32), "w": w}


def apply(params, x, *, train=False, rng=None):
    """x [B, 784] -> logits [B, 10]; no dropout, so `train`/`rng` are
    unused."""
    return x @ params["w"] + params["b"]


def forward_flops() -> int:
    """FLOPs of one sample's forward pass: the 784 x 10 matmul."""
    return 2 * D_IN * D_OUT
