"""FLOP and byte counts of the benchmark against hand-worked numbers."""
import json
import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import flops  # noqa: E402
from bench.harness import find_cell  # noqa: E402
from bench.models import mlp  # noqa: E402


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def n_params(init):
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
    return sum(int(l.size) for l in jax.tree.leaves(tree))


def test_model_sizes_match_the_configs():
    assert n_params(mlp.init) == config("fig2_mnist")["n_params"]


def test_model_sizes_match_the_paper():
    assert n_params(mlp.init) == 7850 == 784 * 10 + 10


def test_forward_flops():
    # the 784 x 10 matmul, 2 FLOPs per multiply-add
    assert mlp.forward_flops() == 2 * 784 * 10 == 15_680


def test_fig2_hop_counts():
    cfg = find_cell("fig2_iid.fused_map").config
    assert flops.n_symbols(cfg) == 3925
    assert flops.hop_macs(cfg, 1) == (4 * 20 * 100 + 4 * 100) * 3925
    assert flops.hop_macs(cfg, 5) == 5 * 32_970_000
    # symbols in and out: 20 users + 4 IS estimates, 4 IS + 1 PS
    assert flops.hop_bytes(cfg, 1) == 8 * 3925 * (20 + 4 + 4 + 1)


def test_round_flops():
    fig2 = find_cell("fig2_iid.fused_map")
    train = 3 * 15_680 * 5 * 20 * 500        # 5 seeds, 20 users, batch 500
    ev = 15_680 * 2000 * 5
    assert flops.round_flops(fig2.config, fig2.traffic, 5) == (
        train + ev + 8 * 5 * 32_970_000)
    # equivalent channel: no matched filter is counted
    equiv = find_cell("fig2_iid.equiv")
    assert flops.round_flops(equiv.config, equiv.traffic, 5) == train + ev
