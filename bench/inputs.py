"""Inputs of one benchmark run.

Everything the program is fed is made here and handed both to the
program and to the plain reference: the users' training shards and the
test set, the model weights of each seed, the network geometry, and the
per-seed PRNG keys.  Data and weights are made on the device, each in
one jitted call; the geometry is a few hundred numbers and is drawn on
the host.

The weights and the round keys follow `--seed`.  The data and the
geometry follow the configuration's `data_seed`, as a sweep's scenario
fixes them while its model seeds vary: the program compiles the test
set and the geometry into its round program as constants, so inputs
that changed with every run would recompile it in every run.

Data are a synthetic stand-in for MNIST / CIFAR-10 of the same shapes
(28x28x1 flattened to 784, or 32x32x3; 10 classes): each class has a
smooth random template, and a sample is its class template times a
random contrast plus pixel noise, so the task is learnable.  The split
is i.i.d.: every user holds n_train / (C * M) samples.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

N_CLASSES = 10


def seed_key(seed: int, stream: int):
    """A PRNG key for one input stream of a run.  Seeds may exceed 32
    bits, so the high word is folded in rather than truncated."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


# streams of `seed_key`
DATA, WEIGHTS, ROUNDS = 1, 2, 3


def model(cfg: dict):
    """The configuration's plain reference model module."""
    return importlib.import_module(f"bench.models.{cfg['model']}")


@partial(jax.jit, static_argnames=("shape", "n_users", "n_per_user",
                                   "n_test", "noise"))
def _make_data(key, *, shape, n_users, n_per_user, n_test, noise):
    kt, ky, ks, kn, kyt, kst, knt = jax.random.split(key, 7)
    t = jax.random.normal(kt, (N_CLASSES,) + shape, jnp.float32)
    if len(shape) == 3:     # blur images so templates are smooth
        for _ in range(3):
            t = 0.25 * (jnp.roll(t, 1, 1) + jnp.roll(t, -1, 1)
                        + jnp.roll(t, 1, 2) + jnp.roll(t, -1, 2))
    axes = tuple(range(1, t.ndim))
    t = t / jnp.max(jnp.abs(t), axis=axes, keepdims=True)

    def draw(ky, ks, kn, n):
        y = jax.random.randint(ky, (n,), 0, N_CLASSES)
        s = jax.random.uniform(ks, (n,), jnp.float32, 0.7, 1.3)
        x = (s.reshape((n,) + (1,) * len(shape)) * t[y]
             + noise * jax.random.normal(kn, (n,) + shape, jnp.float32))
        return x, y.astype(jnp.int32)

    x, y = draw(ky, ks, kn, n_users * n_per_user)
    xte, yte = draw(kyt, kst, knt, n_test)
    return x, y, xte, yte


@dataclass
class Inputs:
    X: jax.Array          # [C, M, n, *shape]
    Y: jax.Array          # [C, M, n] int32
    xte: jax.Array        # [n_test, *shape]
    yte: jax.Array        # [n_test] int32
    params: dict          # each leaf stacked over the S seeds
    keys: jax.Array       # [S, 2] uint32 round keys
    d_mu_is: np.ndarray   # [C, M, C] user (c', m) -> IS c distances
    d_is_ps: np.ndarray   # [C] IS -> PS
    d_mu_ps: np.ndarray   # [C, M] user -> PS


def geometry(seed: int, cfg: dict):
    """Paper Sec. V placement: ISs uniform in angle at a radius drawn
    from `r_cluster` around the PS, users uniform in angle at a radius
    drawn from `r_mu` around their IS."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 4])
    C, M = cfg["C"], cfg["M"]
    ang_c = rng.uniform(0, 2 * np.pi, C)
    rad_c = rng.uniform(*cfg["r_cluster"], C)
    is_xy = np.stack([rad_c * np.cos(ang_c), rad_c * np.sin(ang_c)], -1)
    ang_m = rng.uniform(0, 2 * np.pi, (C, M))
    rad_m = rng.uniform(*cfg["r_mu"], (C, M))
    mu_xy = is_xy[:, None, :] + np.stack(
        [rad_m * np.cos(ang_m), rad_m * np.sin(ang_m)], -1)
    d_mu_is = np.linalg.norm(mu_xy[:, :, None, :] - is_xy[None, None],
                             axis=-1)
    return (np.maximum(d_mu_is, 1e-3), np.linalg.norm(is_xy, axis=-1),
            np.linalg.norm(mu_xy, axis=-1))


def make_inputs(seed: int, cfg: dict, n_seeds: int) -> Inputs:
    C, M = cfg["C"], cfg["M"]
    n_per_user = cfg["n_train"] // (C * M)
    x, y, xte, yte = _make_data(
        seed_key(cfg["data_seed"], DATA), shape=tuple(cfg["sample_shape"]),
        n_users=C * M, n_per_user=n_per_user, n_test=cfg["n_test"],
        noise=float(cfg["pixel_noise"]))
    X = x.reshape((C, M, n_per_user) + x.shape[1:])
    Y = y.reshape(C, M, n_per_user)
    wkeys = jax.random.split(seed_key(seed, WEIGHTS), n_seeds)
    params = jax.jit(jax.vmap(model(cfg).init))(wkeys)
    keys = jax.random.split(seed_key(seed, ROUNDS), n_seeds)
    return Inputs(X, Y, xte, yte, params, keys,
                  *geometry(cfg["data_seed"], cfg))
