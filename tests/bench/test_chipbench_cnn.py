"""`correct` on a small `fig3_cifar.fused` on the CPU: a sound run of
the CNN through the faithful fused hop passes; the bfloat16 control
and a state left unchanged fail.

The CNN keeps its published widths (N = 154,197 symbols, so the fused
kernel runs at the cell's own N, interpreted); the network, the data
and the local steps are cut to what a test run holds: 2 clusters of one
user, 8 antennas, one local step at batch 8.  The limits are the
cell's own.

One sound run through `harness.run_cell` is made for the module; the
float32 reference's rounds it was compared with are kept, and the
control and the state left unchanged are read against them, so the
reference is compiled once for all three checks."""
import dataclasses
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import compare, harness, reference  # noqa: E402
from bench.inputs import make_inputs, model  # noqa: E402

CELL = "fig3_cifar.fused"
SMALL = dict(C=2, M=1, K=8, K_ps=8, n_train=64, n_test=32, batch=8, tau=1)
SEED = 2**31 + 16016


def small():
    cell = harness.find_cell(CELL)
    return dataclasses.replace(cell, config={**cell.config, **SMALL})


@pytest.fixture(scope="module")
def sound():
    """One window only (0 s): the interpreted kernel is slow here.
    Returns the run's result and the float32 reference's runs it was
    compared with."""
    runs = []

    class Kept(reference.Round):
        def run(self, *args, **kwargs):
            runs.append(super().run(*args, **kwargs))
            return runs[-1]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Round", Kept)
        out = harness.run_cell(small(), SEED, 0.0, False,
                               t_start=time.perf_counter())
    return out, runs


@pytest.fixture(scope="module")
def inp():
    return make_inputs(SEED, small().config, 1)


def judged(prog_out, ref_runs, inp):
    """`correct`'s checks of program outputs against the kept reference,
    and the names of the numbers that fail their limit."""
    theta0 = jax.device_get(inp.params)
    checks = compare.judge(compare.readings(prog_out, ref_runs, theta0,
                                            True), small().limits)
    return checks, [k for k, c in checks.items() if c["value"] > c["limit"]]


def stack(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32)[None], tree)


def test_sound_run_is_correct(sound):
    out, runs = sound
    assert out["correct"], out["checks"]
    assert out["attempted"] == 1 and out["failed"] == 0
    assert out["window"]["compiles"] == 0
    assert len(runs) == 1      # S = 1: one float32 reference run


def test_state_unchanged_makes_correct_false(sound, inp):
    """The program's step returning the state it was given: theta after
    three rounds is theta before them, Adam's first moments are still
    nought."""
    _, runs = sound
    losses, opt1, _, _ = runs[0]
    unchanged = {"losses": np.asarray(losses)[:, None],
                 "m1": stack(jax.tree.map(np.zeros_like, opt1["m"])),
                 "theta3": jax.device_get(inp.params)}
    checks, failed = judged(unchanged, runs, inp)
    assert {"update_gap", "moment_gap"} <= set(failed), checks


def test_bfloat16_control_fails(sound, inp):
    """The reference one precision step down in the program's place."""
    _, runs = sound
    cell = small()
    cfg = cell.config
    setup = reference.Setup.from_config(cfg, cell.traffic, inp.d_mu_is,
                                        inp.d_is_ps)
    theta0 = jax.tree.map(lambda a: a[0], inp.params)
    losses, opt1, _, theta3 = reference.control(setup, model(cfg)).run(
        theta0, inp.X, inp.Y, inp.xte, inp.yte, inp.keys[0],
        harness.CHECK_ROUNDS)
    checks, failed = judged({"losses": np.asarray(losses)[:, None],
                             "m1": stack(opt1["m"]),
                             "theta3": stack(theta3)}, runs, inp)
    assert failed, checks
