"""`correct` on a small cell on the CPU: a sound run passes; the
bfloat16 control and each fault the one-chip cells can have fail.

The harness runs here with its look for a chip skipped (it is only in
`bench/run.py`), at a size a test run holds: 2 clusters of 2 users,
8 antennas, batch 16.  The limits are the cells' own."""
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness  # noqa: E402
from bench.calibrate import reference_as_program  # noqa: E402

SMALL = dict(C=2, M=2, K=8, K_ps=8, n_train=400, n_test=100, batch=16)
SEED = 2**31 + 12345


def small(name, **traffic):
    cell = harness.find_cell(name)
    return dataclasses.replace(
        cell, config={**cell.config, **SMALL},
        traffic={**cell.traffic, **traffic})


def run(cell, fault=None):
    return harness.run_cell(cell, SEED, 0.3, False,
                            t_start=time.perf_counter(), fault=fault)


def failed(out):
    return [k for k, c in out["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("name", ["fig2_iid.equiv", "fig2_iid.fused_map"])
def test_sound_run_is_correct(name):
    out = run(small(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["window"]["compiles"] == 0


class StateUnchanged(harness.ProgramFault):
    """The step returns the state it was given."""

    def chunk(self, chunk):
        def broken(st, ks, P, P_is):
            _, ks2, metrics = chunk(jax.tree.map(jnp.copy, st), ks, P, P_is)
            return st, ks2, metrics
        return broken


class HalfBatch(harness.ProgramFault):
    """Each local step's loss is the mean over half of the batch."""

    def config(self, cfg):
        return dataclasses.replace(cfg, batch=cfg.batch // 2)


class AnswerAltered(harness.ProgramFault):
    """The eval loss is taken over half of the test set."""

    def test_set(self, xte, yte):
        return xte[: len(xte) // 2], yte[: len(yte) // 2]


@pytest.mark.parametrize("fault, number", [
    (StateUnchanged(), "update_gap"), (HalfBatch(), "moment_gap"),
    (AnswerAltered(), "loss_gap")])
def test_fault_makes_correct_false(fault, number):
    out = run(small("fig2_iid.equiv"), fault)
    assert not out["correct"]
    assert number in failed(out), out["checks"]


@pytest.mark.parametrize("name", ["fig2_iid.equiv", "fig2_iid.fused_map"])
def test_bfloat16_control_fails(name):
    cell = small(name)
    r = reference_as_program(cell, SEED, True)
    checks = harness.compare.judge(r, cell.limits)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("name", ["fig2_iid.equiv", "fig2_iid.fused_map"])
def test_harness_eval_matches_the_sweeps(name):
    """The eval the harness folds into the chunk program is a copy of
    the sweep's: on the sweep's own final theta it gives the accuracy
    and loss the sweep recorded."""
    from repro.sim.sweep import SweepRunner

    cell = small(name)
    sc = harness.scenario(cell).replace(total_IT=2 * cell.config["I"],
                                        eval_every=1)
    res = SweepRunner([sc], seeds=[3, 4], driver="chunked", keep_state=True,
                      batch=cell.traffic.get("batch", "vmap")
                      ).run_scenario(sc)
    _, apply_fn, _ = sc.task_fns()
    _, _, xte, yte = sc.make_data()
    ev = harness.eval_state_fn(apply_fn, jnp.asarray(xte), jnp.asarray(yte))
    acc, loss, _, _ = jax.jit(jax.vmap(ev))(res.final_state)
    assert [a[-1] for a in res.acc] == pytest.approx(
        [float(a) for a in acc], rel=1e-6)
    assert [l[-1] for l in res.loss] == pytest.approx(
        [float(l) for l in loss], rel=1e-6)


def _readings(theta3):
    """compare.readings for one seed whose theta0 is nought, with the
    reference's change given and the program's `theta3`."""
    import numpy as np

    ref_change = {"b": np.full(10, 0.15), "w": np.full((784, 10), 0.15)}
    grad = {"b": np.ones(10), "w": np.ones((784, 10))}
    ref = [([1.0, 0.5, 0.25], None, grad, ref_change)]
    prog = {"losses": np.array([[1.0], [0.5], [0.25]]), "m1": None,
            "theta3": {k: v[None] for k, v in theta3.items()}}
    zero = {k: np.zeros((1,) + v.shape) for k, v in ref_change.items()}
    return harness.compare.readings(prog, ref, zero, adam=False)


def test_update_gap_holds_a_state_unchanged_and_not_a_bias_flip():
    """A state left unchanged reads 1.  A bias component short by 0.1
    in the aggregate, as when one user's gradient lies within rounding
    of nought and Adam's sign-like first step moves it the other way,
    reads under the limit over the whole change, where the bias leaf
    alone would read over it."""
    import numpy as np

    limit = harness.find_cell("fig2_iid.fused_map").limits["update_gap"]
    same = {"b": np.full(10, 0.15), "w": np.full((784, 10), 0.15)}
    assert _readings(same)["update_gap"] == 0.0
    unchanged = {k: np.zeros_like(v) for k, v in same.items()}
    assert _readings(unchanged)["update_gap"] == pytest.approx(1.0)
    flipped = {**same, "b": np.where(np.arange(10) == 3, 0.05, 0.15)}
    assert 0 < _readings(flipped)["update_gap"] < limit
    keep = np.ones(2, bool)
    assert harness.compare.leaf_gap(flipped, same, keep) > limit
