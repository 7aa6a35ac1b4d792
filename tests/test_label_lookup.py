"""Minibatch label lookup of local training (`repro.core.whfl`).

`make_local_train` looks up a minibatch's labels either with a gather
(``y[idx]``) or with one dense compare-and-select over the user's shard
(`dense_labels`), as `label_lookup` picks from the shard's size.  Pinned
here: the dense lookup is ``y[idx]`` bit for bit (edges, repeats, a
one-sample shard, MNIST- and CIFAR-shaped shards, under the single
engine's vmap and the sharded engine's lax.map); the rule sends fig2's
shard dense and a shard above the crossover to the gather; the compiled
fig2 local step keeps one gather (the rows of X) in its ``whfl.batch``
scope; the runners record the lookup in their ``exec`` metadata.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.whfl import (DENSE_LABELS_MAX_N, WHFLConfig, dense_labels,
                             label_lookup, make_local_train)

# (n, batch) of one user's shard: fig2's MNIST (20,000 samples over 20
# users, batch 500) and fig3's CIFAR-10 (50,000 over 20, batch 128)
SHARDS = {"mnist": (1000, 500), "cifar": (2500, 128)}
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _draw(n, batch, seed, lead=()):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    y = jax.random.randint(k1, lead + (n,), 0, 10, jnp.int32)
    idx = jax.random.randint(k2, lead + (batch,), 0, n, jnp.int32)
    return y, idx


def _assert_bitwise(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shard", SHARDS)
def test_dense_labels_equal_gather(shard):
    y, idx = _draw(*SHARDS[shard], seed=0)
    _assert_bitwise(jax.jit(dense_labels)(y, idx), y[idx])


@pytest.mark.parametrize("case", ["ends_repeated", "one_sample", "uint8"])
def test_dense_labels_edges(case):
    if case == "ends_repeated":
        n = 1000
        y = jnp.arange(n, dtype=jnp.int32) % 10 + 3 * (jnp.arange(n) == 0)
        idx = jnp.array([0, n - 1, 0, n - 1, 5, 5, n - 1, 0, 0], jnp.int32)
    elif case == "one_sample":
        y, idx = jnp.array([7], jnp.int32), jnp.zeros((6,), jnp.int32)
    else:                       # a narrower label dtype keeps its dtype
        y, idx = _draw(300, 64, seed=3)
        y = y.astype(jnp.uint8) + jnp.uint8(245)
    _assert_bitwise(jax.jit(dense_labels)(y, idx), y[idx])


@pytest.mark.parametrize("engine", ["single_vmap", "sharded_map"])
@pytest.mark.parametrize("shard", SHARDS)
def test_dense_labels_under_engine_maps(shard, engine):
    """The single engine vmaps local training over (cluster, user); the
    sharded engine lax.maps it over each shard's clusters and users."""
    y, idx = _draw(*SHARDS[shard], seed=1, lead=(4, 5))
    want = jax.vmap(jax.vmap(lambda a, i: a[i]))(y, idx)
    if engine == "single_vmap":
        fn = jax.vmap(jax.vmap(dense_labels))
    else:
        def fn(y, idx):
            return jax.lax.map(
                lambda c: jax.lax.map(lambda u: dense_labels(*u), c),
                (y, idx))
    _assert_bitwise(jax.jit(fn)(y, idx), want)


def test_rule_sends_fig2_dense_and_large_shards_to_gather():
    assert label_lookup(SHARDS["mnist"][0]) == "dense"
    assert label_lookup(SHARDS["cifar"][0]) == "gather"
    assert label_lookup(1) == "dense"
    assert label_lookup(DENSE_LABELS_MAX_N) == "dense"
    assert label_lookup(DENSE_LABELS_MAX_N + 1) == "gather"
    assert label_lookup(100 * DENSE_LABELS_MAX_N) == "gather"


def _batch_scope_gathers(n, batch, C, M):
    """Gathers named in the ``whfl.batch`` scope of the compiled local
    step, vmapped over (cluster, user) as the single engine runs it."""
    from repro.nn.core import split_params
    from repro.optim import adam
    from repro.sim.scenario import TASKS

    init_fn, _, loss_fn, _ = TASKS["mnist"]
    opt = adam(5e-2)
    params = split_params(init_fn(jax.random.PRNGKey(0)))[0]
    local_train = make_local_train(loss_fn, opt, WHFLConfig(batch=batch))
    train_c = jax.vmap(lambda th, st, x, y, k: local_train(th, st, x, y, k, 0),
                       in_axes=(None, 0, 0, 0, 0))

    def shape_of(tree, lead):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(lead + a.shape, a.dtype), tree)

    compiled = jax.jit(jax.vmap(train_c)).lower(
        shape_of(params, (C,)), shape_of(opt.init(params), (C, M)),
        jax.ShapeDtypeStruct((C, M, n, 784), jnp.float32),
        jax.ShapeDtypeStruct((C, M, n), jnp.int32),
        jax.ShapeDtypeStruct((C, M, 2), jnp.uint32)).compile()
    return sum(1 for line in compiled.as_text().splitlines()
               if " gather(" in line
               and any("whfl.batch" in o for o in OP_NAME.findall(line)))


def test_fig2_local_step_gathers_rows_only():
    """fig2 at C = 4, M = 5, n = 1,000, batch 500: the rows of X are the
    only gather left in ``whfl.batch`` (the label gather was the other)."""
    assert _batch_scope_gathers(*SHARDS["mnist"], C=4, M=5) == 1


def test_shard_above_crossover_keeps_label_gather():
    assert _batch_scope_gathers(DENSE_LABELS_MAX_N + 1, 8, C=1, M=2) == 2


def test_runners_record_label_lookup():
    from repro.exec import make_runner
    from repro.sim import get_scenario
    from repro.sim.sweep import SweepRunner

    sc = get_scenario("fig2_iid")
    topo = sc.make_topology()
    n = SHARDS["mnist"][0]
    single = SweepRunner([sc])
    sharded = make_runner("sharded", [sc], mesh="1x1")
    assert single._exec_info(topo, n_user=n)["label_lookup"] == "dense"
    info = sharded._exec_info(topo, two_n=7850, n_user=n)
    assert info["label_lookup"] == "dense" and info["name"] == "sharded"
    big = DENSE_LABELS_MAX_N + 1
    assert single._exec_info(n_user=big)["label_lookup"] == "gather"
    assert sharded._exec_info(n_user=big)["label_lookup"] == "gather"
    assert "label_lookup" not in single._exec_info()

    quick = sc.quick().replace(total_IT=1, eval_every=1)
    res = SweepRunner([quick], seeds=1).run()[0]
    n_quick = quick.make_data()[0].shape[2]
    assert res.to_record()["exec"]["label_lookup"] == label_lookup(n_quick)
    assert res.exec_info["label_lookup"] == "dense"
