"""Device time by the round's named phases (`bench/scopes.py`): the
HLO-text map from op to scope, the split of a window's busy time, and
the per-scope readers.  The split is checked on a small excerpt
recorded from a TPU v5e run of `fig2_iid.equiv` (the events
`bench.trace.load` reads, with the op->scope map of the chunk program
that ran, under "scopes")."""
import dataclasses
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import harness, scopes, trace  # noqa: E402

NEW = ("train_ms", "batch_ms", "cluster_hop_ms", "ps_hop_ms", "eval_ms")


def load(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


EQUIV = load("fig2_equiv_trace_excerpt.json")
FUSED = load("fig2_fused_trace_excerpt.json")

HLO = """\
HloModule jit_chunk, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.7 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %sine.3 = f32[8]{0} sine(%param_0.1), metadata={op_name="jit(chunk)/whfl.train/vmap(whfl.cluster_hop)/sin"}
  ROOT %gather.2 = f32[8]{0} negate(%sine.3), metadata={op_name="jit(chunk)/vmap(whfl.train)/whfl.batch/gather"}
}

%fused_computation.8 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %copy.5 = f32[8]{0} copy(%param_0.2)
}

ENTRY %main.9 (X.1: f32[8]) -> f32[8] {
  %X.1 = f32[8]{0} parameter(0), metadata={op_name="X"}
  %fusion.211 = f32[8]{0} fusion(%X.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(chunk)/whfl.update/add"}
  %fusion.3 = f32[8]{0} fusion(%fusion.211), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(chunk)/whfl.ps_hop/mul"}
  %fused_mac.25 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/whfl.cluster_hop/jit(fused_mac)/pallas_call"}
  ROOT %reduce.4 = f32[8]{0} copy(%fused_mac.25), metadata={op_name="jit(chunk)/whfl.evaluate/whfl.py/mul"}
}
"""


def ctx_for(events, scope_map, rounds=1):
    return SimpleNamespace(cell=None, events=events, scopes=scope_map,
                           window=SimpleNamespace(rounds=rounds))


def test_hlo_map_gives_a_fusion_its_roots_scope():
    m = scopes.op_scopes(HLO)
    assert m["fusion.211"] == "whfl.batch"       # root, not its own
    assert m["fusion.3"] == "whfl.ps_hop"        # root names none: own
    assert m["fused_mac.25"] == "whfl.cluster_hop"
    assert m["sine.3"] == "whfl.cluster_hop"     # innermost scope
    assert m["X.1"] == m["copy.5"] == m["reduce.4"] == scopes.OTHER


def test_hlo_map_of_a_compiled_program_finds_its_scopes():
    import jax
    import jax.numpy as jnp

    def f(x, idx):
        with jax.named_scope("whfl.train"):
            with jax.named_scope("whfl.batch"):
                xb = x[idx]
            g = jnp.tanh(xb).sum(0)
        with jax.named_scope("whfl.eval"):
            return jnp.sin(g) * 2

    text = jax.jit(f).lower(jnp.ones((16, 4)),
                            jnp.arange(8)).compile().as_text()
    found = set(scopes.op_scopes(text).values())
    assert {"whfl.batch", "whfl.eval"} <= found


@pytest.mark.parametrize("events,map_", [
    (EQUIV, EQUIV["scopes"]),
    (FUSED, {}),                      # a program naming no phase
])
def test_scopes_and_other_sum_to_busy(events, map_):
    split = scopes.scope_ns(events, map_)
    assert sum(split.values()) == pytest.approx(
        trace.busy_ns(events, "0"), abs=2)
    assert all(t >= 0 for t in split.values())
    if not map_:
        assert set(split) == {scopes.OTHER}


def test_equiv_excerpt_time_sits_in_the_named_phases():
    split = scopes.scope_ns(EQUIV, EQUIV["scopes"])
    busy = trace.busy_ns(EQUIV, "0")
    assert set(split) <= set(scopes.SCOPES) | {scopes.OTHER}
    assert split["whfl.batch"] > 0.5 * busy   # the minibatch gather leads
    assert sum(t for s, t in split.items() if s != scopes.OTHER) \
        >= 0.8 * busy


def test_readers_split_the_excerpt_per_round():
    ctx = ctx_for(EQUIV, EQUIV["scopes"], rounds=5)   # rounds in it
    got = {n: harness.metric_reader(n)(ctx) for n in NEW}
    split = scopes.scope_ns(EQUIV, EQUIV["scopes"])
    assert got["batch_ms"] == pytest.approx(
        1e-6 * split["whfl.batch"] / 5)
    assert got["train_ms"] == pytest.approx(
        1e-6 * (split.get("whfl.train", 0) + split["whfl.batch"]) / 5)
    assert all(v is not None and v >= 0 for v in got.values())


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_without_trace_events(name):
    ctx = ctx_for({}, EQUIV["scopes"])
    assert harness.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_the_program_names_no_phase(name):
    ctx = ctx_for(FUSED, {"while.29": scopes.OTHER})
    assert harness.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("name", ["fig2_iid.equiv", "fig2_iid.fused_map"])
def test_cell_chunk_program_maps_every_scope(name):
    cell = harness.find_cell(name)
    cell = dataclasses.replace(cell, config={
        **cell.config, "C": 2, "M": 2, "K": 8, "K_ps": 8, "n_train": 400,
        "n_test": 100, "batch": 16})
    ctx = SimpleNamespace(cell=cell)
    assert set(scopes.run_scopes(ctx).values()) == \
        set(scopes.SCOPES) | {scopes.OTHER}
