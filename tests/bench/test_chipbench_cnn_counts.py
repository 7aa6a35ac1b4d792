"""The CNN cell's counts against hand-worked numbers, and the model
scopes (`bench/model_scopes.py`) behind `conv_ms` and `conv_mfu`."""
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import flops, harness, model_scopes, scopes, trace  # noqa: E402
from bench.models import cnn  # noqa: E402
from repro.models.paper_models import (cifar_apply, cifar_init,  # noqa: E402
                                       n_params)

CELL = "fig3_cifar.fused"
# per-sample forward FLOPs of the six convs: 2 x H x W x 9 x cin x cout
CONV = [2 * 32 * 32 * 9 * 3 * 32, 2 * 32 * 32 * 9 * 32 * 32,
        2 * 16 * 16 * 9 * 32 * 64, 2 * 16 * 16 * 9 * 64 * 64,
        2 * 8 * 8 * 9 * 64 * 128, 2 * 8 * 8 * 9 * 128 * 128]

with open(os.path.join(HERE, "data", "fig2_equiv_trace_excerpt.json")) as f:
    EQUIV = json.load(f)


def test_forward_flops():
    assert cnn.layer_flops() == CONV == [1_769_472, 18_874_368, 9_437_184,
                                         18_874_368, 9_437_184, 18_874_368]
    assert cnn.conv_flops() == 77_266_944
    assert cnn.forward_flops() == 77_266_944 + 2 * 2048 * 10 == 77_307_904


def test_parameter_count():
    """307,498 weights and biases plus 896 batch-norm scales and biases,
    in the config, the reference and the program's model alike."""
    cfg = harness.find_cell(CELL).config
    ref = jax.eval_shape(cnn.init, jax.random.PRNGKey(0))
    prog = cifar_init(jax.random.PRNGKey(0))
    assert cfg["n_params"] == 308_394 == 307_498 + 2 * (32 + 32 + 64 + 64
                                                        + 128 + 128)
    assert sum(int(l.size) for l in jax.tree.leaves(ref)) == 308_394
    assert n_params(prog) == 308_394


def test_fig3_hop_counts():
    cfg = harness.find_cell(CELL).config
    assert flops.n_symbols(cfg) == 154_197
    # 4 ISs hear 20 users on 100 antennas; the PS hears 4 ISs on 100
    assert flops.hop_macs(cfg, 1) == (4 * 20 * 100 + 4 * 100) * 154_197 \
        == 1_295_254_800
    assert flops.hop_bytes(cfg, 1) == 8 * 154_197 * (20 + 4 + 4 + 1)


def test_round_flops_is_the_sum_of_its_parts():
    cell = harness.find_cell(CELL)
    train = 3 * 77_307_904 * 20 * 5 * 128      # S=1, 20 users, tau 5
    ev = 77_307_904 * 10_000
    hop = 8 * 1_295_254_800
    assert flops.train_flops(cell.config, 1) == train
    assert flops.eval_flops(cell.config, 1) == ev
    assert flops.round_flops(cell.config, cell.traffic, 1) == \
        train + ev + hop == 3_752_064_592_000


def test_conv_round_flops():
    """Forward and both gradients of every conv for each of the 12,800
    training samples, but the first conv's input gradient; one forward
    over the 10,000 test samples."""
    cfg = harness.find_cell(CELL).config
    per_sample = 3 * sum(CONV) - CONV[0]
    assert cnn.conv_round_flops(cfg, 1) == \
        per_sample * 12_800 + sum(CONV) * 10_000 == 3_717_070_848_000
    assert cnn.conv_round_flops(cfg, 2) == 2 * cnn.conv_round_flops(cfg, 1)


HLO = """\
HloModule jit_chunk, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %convolution.3 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(chunk)/whfl.train/vmap(whfl.train)/while/body/transpose(jvp(cnn.conv))/conv_general_dilated"}
}

%fused_computation.4 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %convolution.4 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(chunk)/whfl.eval/vmap(cnn.conv)/conv_general_dilated"}
  ROOT %multiply.2 = f32[8]{0} multiply(%convolution.4, %convolution.4), metadata={op_name="jit(chunk)/whfl.eval/vmap()/mul"}
}

ENTRY %main.9 (X.1: f32[8]) -> f32[8] {
  %X.1 = f32[8]{0} parameter(0), metadata={op_name="X"}
  %fusion.1 = f32[8]{0} fusion(%X.1), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(chunk)/whfl.train/add"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kOutput, calls=%fused_computation.4, metadata={op_name="jit(chunk)/whfl.eval/mul"}
  ROOT %add.4 = f32[8]{0} add(%fusion.2, %X.1), metadata={op_name="jit(chunk)/whfl.train/cnn.convolve/add"}
}
"""


def test_hlo_map_reads_the_model_scope():
    m = model_scopes.op_scopes(HLO)
    assert m["fusion.1"] == "cnn.conv"           # its root's scope
    assert m["fusion.2"] == model_scopes.OTHER   # root names none: own
    assert m["convolution.4"] == "cnn.conv"
    assert m["add.4"] == "cnn.convolve"          # a name, not a prefix
    assert m["X.1"] == model_scopes.OTHER
    # the round's phases are read from the same text as before
    assert scopes.op_scopes(HLO)["fusion.1"] == "whfl.train"


def test_compiled_model_names_its_convs_inside_the_phase():
    """The program's CNN: forward and backward convs carry `cnn.conv`,
    and stay in their round phase."""
    def f(p, x):
        with jax.named_scope("whfl.train"):
            return jax.grad(lambda p: jnp.sum(cifar_apply(p, x)))(p)

    x = jnp.ones((2, 32, 32, 3))
    params = cnn.init(jax.random.PRNGKey(0))
    text = jax.jit(f).lower(params, x).compile().as_text()
    lines = [l for l in text.splitlines() if "cnn.conv" in l]
    assert any("transpose(jvp(cnn.conv))" in l for l in lines)
    assert set(model_scopes.op_scopes(text).values()) == {
        "cnn.conv", model_scopes.OTHER}
    assert all("whfl.train" in l for l in lines)


def ctx_for(events, model_map, rounds=5):
    cell = harness.find_cell(CELL)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return SimpleNamespace(cell=cell, events=events, model_scopes=model_map,
                           window=SimpleNamespace(rounds=rounds), seeds=1,
                           peaks=peaks)


def test_conv_readers_on_a_recorded_excerpt():
    """Ops of the excerpt put in `cnn.conv` by hand: `conv_ms` is their
    self time per round, `conv_mfu` the conv FLOPs over it."""
    fake = {"fusion.1": "cnn.conv", "fusion": "cnn.conv"}
    ctx = ctx_for(EQUIV, fake)
    split = scopes.scope_ns(EQUIV, fake)
    ms = harness.metric_reader("conv_ms")(ctx)
    assert ms == pytest.approx(1e-6 * split["cnn.conv"] / 5)
    assert 0 < ms < 1e-6 * trace.busy_ns(EQUIV, "0") / 5
    mfu = harness.metric_reader("conv_mfu")(ctx)
    assert mfu == pytest.approx(
        100 * 3_717_070_848_000 / (ms * 1e-3 * 197e12))


@pytest.mark.parametrize("name", ["conv_ms", "conv_mfu"])
def test_conv_readers_read_nothing_without_trace_or_names(name):
    assert harness.metric_reader(name)(ctx_for({}, {"fusion.1": "x"})) \
        is None
    # a program older than the names: every op is `other`
    older = ctx_for(EQUIV, {"fusion.1": model_scopes.OTHER})
    assert harness.metric_reader(name)(older) is None
