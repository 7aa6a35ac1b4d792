"""The benchmark's plain CNN reference (`bench/models/cnn.py`) against
the model the sweep trains (`repro.models.paper_models`): the same
parameter tree, and on the reference's seeded weights the same logits
in eval and in training (dropout at a fixed key) and the same
gradients of the loss, to float32 round-off.  The program side runs at
the CPU's float32; the reference, as in the benchmark, at the highest
matmul precision."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import reference  # noqa: E402
from bench.models import cnn  # noqa: E402
from repro.models.paper_models import cifar_apply, cifar_init  # noqa: E402
from repro.nn.core import split_params  # noqa: E402
from repro.sim.scenario import TASKS  # noqa: E402

B = 8
RTOL = 2e-5     # of the largest |value|: float32 sums taps in other orders


@pytest.fixture(scope="module")
def setup():
    params = cnn.init(jax.random.PRNGKey(2**31 + 5))
    kx, ky = jax.random.split(jax.random.PRNGKey(11))
    x = jax.random.normal(kx, (B, 32, 32, 3), jnp.float32)
    y = jax.random.randint(ky, (B,), 0, 10)
    return params, x, y


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-30)
    np.testing.assert_allclose(a, b, rtol=0, atol=rtol * scale)


def test_parameter_tree_matches_the_programs():
    prog = jax.eval_shape(lambda k: split_params(cifar_init(k))[0],
                          jax.random.PRNGKey(0))
    ref = jax.eval_shape(cnn.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert [l.shape for l in jax.tree.leaves(prog)] == [
        l.shape for l in jax.tree.leaves(ref)]


@pytest.mark.parametrize("train", [False, True])
def test_logits_match_the_programs(setup, train):
    params, x, _ = setup
    rng = jax.random.PRNGKey(7) if train else None
    prog = jax.jit(lambda p, x: cifar_apply(p, x, train=train, rng=rng))(
        params, x)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: cnn.apply(p, x, train=train, rng=rng))(
            params, x)
    _close(prog, ref)


def test_dropout_draws_differ_by_key(setup):
    """Train-mode logits follow the dropout key, so the comparison above
    sees the program's masks and not just the eval path."""
    params, x, _ = setup
    f = jax.jit(lambda k: cnn.apply(params, x, train=True, rng=k))
    assert not np.allclose(f(jax.random.PRNGKey(7)), f(jax.random.PRNGKey(8)))


def test_gradients_match_the_programs(setup):
    params, x, y = setup
    kd = jax.random.PRNGKey(3)
    loss_fn = TASKS["cifar"][2]
    prog = jax.jit(jax.grad(loss_fn))(params, x, y, kd)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.grad(lambda p: reference._xent(
            cnn.apply(p, x, train=True, rng=kd), y)))(params)
    for (path, g_r), g_p in zip(jax.tree_util.tree_flatten_with_path(ref)[0],
                                jax.tree.leaves(prog)):
        name = jax.tree_util.keystr(path)
        if name.startswith("['conv']") and name.endswith("['b']"):
            # a conv bias ahead of batch-norm: its gradient is nought up
            # to round-off, in both
            assert float(jnp.max(jnp.abs(g_r))) < 1e-5, name
            assert float(jnp.max(jnp.abs(g_p))) < 1e-5, name
            continue
        _close(g_p, g_r)
