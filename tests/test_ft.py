"""`repro.ft` tests: checkpoint/resume parity, deterministic fault
injection, and the non-finite guard.

The acceptance bar of the fault-tolerance layer is bitwise:
1. a run killed at round k and resumed reproduces the uninterrupted
   run exactly (metrics AND the full final carry), on both drivers,
2. every feature's OFF position (guard="off", checkpoint=None,
   faults=None) is a Python-level no-op — trajectories equal a build
   without the feature,
3. faults fire deterministically (same round/window/attempt on every
   engine), so the recovery paths themselves are testable.

Multi-device / cross-mesh resume lives in CI (resume-parity job) and
`test_ft_cross_mesh_resume` (slow tier).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.ft import (CRASH_EXIT_CODE, CheckpointManager, FaultPlan,
                      GradPoison, backoff_delay, check_manifest,
                      guard_estimate, scenario_fingerprint,
                      validate_guard)
from repro.obs.trace import validate_trace
from repro.sim import SweepRunner, get_scenario

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_fig2(**kw):
    sc = get_scenario("fig2_iid").quick().replace(total_IT=6,
                                                  eval_every=2)
    return sc.replace(**kw) if kw else sc


def _tree_bitwise_equal(a, b):
    eq = jax.tree.map(lambda x, y: bool(np.array_equal(np.asarray(x),
                                                       np.asarray(y))),
                      a, b)
    return jax.tree.all(eq)


# ---------------------------------------------------------------------------
# FaultPlan / backoff / guard units
# ---------------------------------------------------------------------------

def test_fault_plan_parse():
    fp = FaultPlan.parse("crash_round=5,save_errors=2,poison=nan@4:0:1")
    assert fp.crash_round == 5 and fp.save_errors == 2
    assert fp.poison == GradPoison(t=4, c=0, m=1, mode="nan")
    assert np.isnan(fp.poison.value)
    assert FaultPlan.parse("poison=inf@1:2:3").poison.mode == "inf"
    assert np.isinf(FaultPlan.parse("poison=inf@1:2:3").poison.value)
    assert FaultPlan().is_empty and not fp.is_empty


@pytest.mark.parametrize("spec", [
    "crash_round", "crash_round=0", "whatever=3", "poison=nan",
    "poison=nan@1:2", "poison=bogus@1:2:3", "save_errors=-1",
])
def test_fault_plan_parse_rejects(spec):
    with pytest.raises(ValueError):
        FaultPlan.parse(spec)


def test_backoff_delay_deterministic_and_exponential():
    d = [backoff_delay(a, base=0.05, seed=0) for a in range(4)]
    assert d == [backoff_delay(a, base=0.05, seed=0) for a in range(4)]
    for a, v in enumerate(d):   # base*2^a <= v < 2*base*2^a
        assert 0.05 * 2 ** a <= v < 0.05 * 2 ** (a + 1)
    assert backoff_delay(1, 0.05, seed=1) != d[1]


def test_guard_estimate_policies():
    import jax.numpy as jnp
    est = jnp.array([[1.0, jnp.nan, 3.0], [4.0, 5.0, 6.0]])
    zf, trip = guard_estimate(est, "zero_fill")
    assert int(trip) == 1
    np.testing.assert_array_equal(
        np.asarray(zf), [[1.0, 0.0, 3.0], [4.0, 5.0, 6.0]])
    for pol in ("skip_round", "halt"):
        sk, trip = guard_estimate(est, pol)
        assert int(trip) == 1
        np.testing.assert_array_equal(np.asarray(sk), np.zeros((2, 3)))
    ok = jnp.array([1.0, -2.0, 0.5])
    out, trip = guard_estimate(ok, "zero_fill")
    assert int(trip) == 0
    # exact selection: finite data passes through bitwise
    assert np.array_equal(np.asarray(out), np.asarray(ok))
    with pytest.raises(ValueError):
        guard_estimate(ok, "off")
    with pytest.raises(ValueError):
        validate_guard("explode")


def test_check_manifest_mismatches():
    sc = _tiny_fig2()
    fp = scenario_fingerprint(sc.to_json())
    man = {"schema": "repro.ft.ckpt/v1", "fingerprint": fp,
           "seeds": [0, 1], "rounds_total": 6, "jax_version": "0"}
    check_manifest(man, fp, [0, 1], 6)             # ok
    with pytest.raises(ValueError, match="seed"):
        check_manifest(man, fp, [0, 1, 2], 6)
    with pytest.raises(ValueError, match="scenario"):
        check_manifest(man, "deadbeef00000000", [0, 1], 6)
    with pytest.raises(ValueError, match="total"):
        check_manifest(man, fp, [0, 1], 9)
    with pytest.raises(ValueError, match="schema"):
        check_manifest({**man, "schema": "v0"}, fp, [0, 1], 6)
    with pytest.warns(UserWarning, match="jax"):
        check_manifest(man, fp, [0, 1], 6, jax_version="9.9")


def test_checkpoint_manager_retries_then_raises(tmp_path):
    """save_errors <= retries recovers (with journaled fault events and
    deterministic backoff); save_errors > retries surfaces the OSError."""
    naps, events = [], []
    mgr = CheckpointManager(str(tmp_path / "ok"), retries=3,
                            faults=FaultPlan(save_errors=2),
                            emit=lambda ev, **f: events.append((ev, f)),
                            sleep=naps.append)
    mgr.save(1, {"x": np.arange(3.0)}, {"round": 1})
    assert mgr.saves == 1 and mgr.io_retries == 2
    assert naps == [backoff_delay(0, 0.05), backoff_delay(1, 0.05)]
    kinds = [f.get("kind") for ev, f in events if ev == "fault"]
    assert kinds == ["ckpt_io_error", "ckpt_io_error"]
    assert events[-1][0] == "checkpoint"
    assert events[-1][1]["attempts"] == 3

    mgr = CheckpointManager(str(tmp_path / "bad"), retries=1,
                            faults=FaultPlan(save_errors=5),
                            sleep=lambda s: None)
    with pytest.raises(OSError, match="injected"):
        mgr.save(1, {"x": np.arange(3.0)}, {"round": 1})


# ---------------------------------------------------------------------------
# OFF is a no-op (bitwise)
# ---------------------------------------------------------------------------

def test_guard_and_checkpoint_off_positions_are_noops(tmp_path):
    """guard=zero_fill without faults and checkpoint-on both reproduce
    the plain run bitwise (metrics + final carry) — the fences pin the
    guard to exact selection and checkpointing never touches device
    state."""
    sc = _tiny_fig2()
    plain = SweepRunner([sc], seeds=2, batch="map",
                        keep_state=True).run_scenario(sc)
    guarded = SweepRunner([sc], seeds=2, batch="map", keep_state=True,
                          guard="zero_fill").run_scenario(sc)
    ck = SweepRunner([sc], seeds=2, batch="map", keep_state=True,
                     checkpoint=str(tmp_path / "ck")).run_scenario(sc)

    for other in (guarded, ck):
        assert other.rounds == plain.rounds
        assert other.acc == plain.acc
        assert other.loss == plain.loss
        assert other.edge_power == plain.edge_power
        assert other.is_power == plain.is_power
    assert _tree_bitwise_equal(ck.final_state, plain.final_state)
    # the guarded run carries one extra (all-zero) trip counter
    g_state = dict(guarded.final_state)
    assert int(np.sum(np.asarray(g_state.pop("guard_trips")))) == 0
    assert _tree_bitwise_equal(g_state, plain.final_state)
    assert guarded.exec_info["guard_trips"] == 0
    assert not guarded.exec_info["guard_halted"]
    assert ck.exec_info["ckpt_saves"] == len(plain.rounds)


# ---------------------------------------------------------------------------
# poison -> guard behavior
# ---------------------------------------------------------------------------

def test_poison_without_guard_goes_non_finite():
    sc = _tiny_fig2()
    res = SweepRunner([sc], seeds=1, batch="map",
                      faults=FaultPlan.parse("poison=nan@2:0:1")
                      ).run_scenario(sc)
    assert not np.isfinite(res.loss[0][-1])


@pytest.mark.parametrize("mode", ["nan", "inf"])
def test_poison_with_zero_fill_guard_stays_finite(mode):
    sc = _tiny_fig2()
    res = SweepRunner([sc], seeds=1, batch="map", guard="zero_fill",
                      faults=FaultPlan(poison=GradPoison(2, 0, 1, mode))
                      ).run_scenario(sc)
    assert np.isfinite(res.loss[0]).all()
    assert res.exec_info["guard_trips"] >= 1
    assert not res.exec_info["guard_halted"]
    assert res.rounds[-1] == sc.rounds     # kept driving


def test_poison_with_halt_guard_stops_early():
    sc = _tiny_fig2()
    res = SweepRunner([sc], seeds=1, batch="map", guard="halt",
                      faults=FaultPlan.parse("poison=nan@2:0:1")
                      ).run_scenario(sc)
    assert res.exec_info["guard_halted"]
    assert res.rounds[-1] < sc.rounds      # stopped at a boundary
    assert np.isfinite(res.loss[0]).all()


def test_poison_out_of_range_raises():
    sc = _tiny_fig2()
    with pytest.raises(ValueError, match="poison"):
        SweepRunner([sc], seeds=1, batch="map",
                    faults=FaultPlan.parse("poison=nan@1:99:0")
                    ).run_scenario(sc)


# ---------------------------------------------------------------------------
# checkpoint/resume parity (in-process; the subprocess kill lives below)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("driver", ["stepwise", "chunked"])
def test_resume_mid_run_is_bitwise(tmp_path, driver):
    """Cut checkpoints every window, drop everything after round 3,
    resume — metrics and the full final carry equal the uninterrupted
    run bitwise.  (The same invariant the CI kill-and-resume job gates
    via `repro.obs.diff --max-ulp 0` with a real SIGKILL.)"""
    sc = _tiny_fig2()
    ckdir = str(tmp_path / "ck")
    ref = SweepRunner([sc], seeds=2, batch="map", driver=driver,
                      keep_state=True).run_scenario(sc)
    full = SweepRunner([sc], seeds=2, batch="map", driver=driver,
                       keep_state=True, checkpoint=ckdir,
                       ckpt_every=1).run_scenario(sc)
    assert _tree_bitwise_equal(full.final_state, ref.final_state)

    # simulate the crash: only the round-3 checkpoint survives (the
    # eval boundaries of T=6, eval_every=2 are rounds 1, 3, 5, 6)
    scdir = os.path.join(ckdir, sc.name)
    assert "round_3.npz" in os.listdir(scdir)
    for f in os.listdir(scdir):
        if f != "round_3.npz":
            os.unlink(os.path.join(scdir, f))
    res = SweepRunner([sc], seeds=2, batch="map", driver=driver,
                      keep_state=True, checkpoint=ckdir,
                      resume=True).run_scenario(sc)
    assert res.exec_info["resumed_from"] == 3
    assert res.rounds == ref.rounds
    assert res.acc == ref.acc and res.loss == ref.loss
    assert res.edge_power == ref.edge_power
    assert res.is_power == ref.is_power
    assert _tree_bitwise_equal(res.final_state, ref.final_state)


def test_resume_from_final_checkpoint_drives_zero_rounds(tmp_path):
    sc = _tiny_fig2()
    ckdir = str(tmp_path / "ck")
    ref = SweepRunner([sc], seeds=1, batch="map", keep_state=True,
                      checkpoint=ckdir).run_scenario(sc)
    res = SweepRunner([sc], seeds=1, batch="map", keep_state=True,
                      checkpoint=ckdir, resume=True).run_scenario(sc)
    assert res.exec_info["resumed_from"] == sc.rounds
    assert res.exec_info["dispatches"] == 0
    assert res.acc == ref.acc and res.rounds == ref.rounds
    assert _tree_bitwise_equal(res.final_state, ref.final_state)


def test_resume_without_checkpoint_is_fresh_start(tmp_path):
    sc = _tiny_fig2()
    ref = SweepRunner([sc], seeds=1, batch="map").run_scenario(sc)
    res = SweepRunner([sc], seeds=1, batch="map",
                      checkpoint=str(tmp_path / "empty"),
                      resume=True).run_scenario(sc)
    assert res.exec_info["resumed_from"] == 0
    assert res.acc == ref.acc


def test_resume_rejects_mismatched_run(tmp_path):
    sc = _tiny_fig2()
    ckdir = str(tmp_path / "ck")
    SweepRunner([sc], seeds=2, batch="map",
                checkpoint=ckdir).run_scenario(sc)
    with pytest.raises(ValueError, match="seed"):
        SweepRunner([sc], seeds=3, batch="map", checkpoint=ckdir,
                    resume=True).run_scenario(sc)
    with pytest.raises(ValueError, match="guard"):
        SweepRunner([sc], seeds=2, batch="map", checkpoint=ckdir,
                    resume=True, guard="zero_fill").run_scenario(sc)
    other = _tiny_fig2(lr=sc.lr * 2)
    with pytest.raises(ValueError, match="fingerprint"):
        SweepRunner([other.replace(name=sc.name)], seeds=2, batch="map",
                    checkpoint=ckdir, resume=True
                    ).run_scenario(other.replace(name=sc.name))


def test_runner_validates_ft_kwargs():
    sc = _tiny_fig2()
    with pytest.raises(ValueError, match="ckpt_every"):
        SweepRunner([sc], checkpoint="/tmp/x", ckpt_every=0)
    with pytest.raises(ValueError, match="resume"):
        SweepRunner([sc], resume=True)
    with pytest.raises(ValueError, match="guard"):
        SweepRunner([sc], guard="sometimes")


def test_cli_rejects_orphan_checkpoint_knobs(tmp_path):
    """Regression: `--ckpt-every N` without `--checkpoint` used to be
    silently ignored — the user believes checkpoints are being cut and
    none are.  All three orphan/degenerate knob combinations must exit
    with an argparse usage error (exit 2) before any work runs."""
    from repro.sim.sweep import main
    for args in (["--ckpt-every", "2"],
                 ["--resume"],
                 ["--ckpt-every", "0", "--checkpoint", "ck"]):
        with pytest.raises(SystemExit) as e:
            main(["--scenarios", "fig2_iid", "--quick"] + args)
        assert e.value.code == 2, args


def test_cli_trace_closes_on_midsweep_failure(tmp_path):
    """Regression: a sweep that dies after the TraceWriter opened used
    to leak the journal without a `run_end` — the try/finally must
    close it so the partial journal stays machine-readable
    (`validate_trace --allow-truncated-tail` semantics: balanced or
    truncated scenarios, but a terminated run)."""
    from repro.sim.sweep import main
    path = str(tmp_path / "t.jsonl")
    with pytest.raises(SystemExit):
        main(["--scenarios", "no_such_scenario", "--trace", path])
    lines = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert lines, "journal was never written"
    assert lines[-1]["event"] == "run_end", lines[-1]


# ---------------------------------------------------------------------------
# the real thing: injected hard crash in a subprocess, then --resume
# ---------------------------------------------------------------------------

def _sweep_cli(args, tmp, check=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.sim.sweep", "--scenarios",
         "fig2_iid", "--quick", "--seeds", "2", "--batch", "map"]
        + args, env=env, capture_output=True, text=True, cwd=str(tmp),
        timeout=1200)
    if check:
        assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out


def test_kill_at_round_and_resume_bitwise_cli(tmp_path):
    """End-to-end acceptance: `--inject crash_round=5` hard-exits the
    process (exit 173) after the round-5 checkpoint; `--resume`
    completes the sweep; metrics and the `--state-out` carry are
    bitwise the uninterrupted run's.  The crash-torn trace journal
    validates under --allow-truncated-tail (per-line fsync)."""
    ref = _sweep_cli(["--out", "ref.json", "--state-out",
                      "ref_state.json"], tmp_path)
    assert "wrote" in ref.stdout

    crash = _sweep_cli(
        ["--checkpoint", "ck", "--ckpt-every", "1", "--inject",
         "crash_round=5", "--trace", "crash.jsonl", "--out",
         "never.json"], tmp_path, check=False)
    assert crash.returncode == CRASH_EXIT_CODE, (
        crash.stdout + "\n" + crash.stderr)
    assert not (tmp_path / "never.json").exists()
    saved = sorted(os.listdir(tmp_path / "ck" / "fig2_iid"))
    assert "round_5.npz" in saved

    # the torn journal: strict validation fails, post-crash audit passes
    counts, errors = validate_trace(str(tmp_path / "crash.jsonl"))
    assert errors
    counts, errors = validate_trace(str(tmp_path / "crash.jsonl"),
                                    allow_truncated_tail=True)
    assert errors == [], errors
    assert counts.get("checkpoint", 0) >= 1
    assert counts.get("fault", 0) == 1

    _sweep_cli(["--checkpoint", "ck", "--resume", "--out", "res.json",
                "--state-out", "res_state.json"], tmp_path)
    for name in ("", "_state"):
        a = json.load(open(tmp_path / f"ref{name}.json"))
        b = json.load(open(tmp_path / f"res{name}.json"))
        sa, sb = a["scenarios"][0], b["scenarios"][0]
        if name:
            assert sa["state"] == sb["state"]    # exact JSON floats
        else:
            assert sa["metrics"] == sb["metrics"]
            assert sa["rounds"] == sb["rounds"]
            assert sb["exec"]["resumed_from"] == 5


@pytest.mark.parametrize("scenario", ["fig2_iid", "fig2_iid_conventional"])
def test_guard_poison_telemetry_single_vs_padded_sharded_bitwise(scenario):
    """The guard, the poison and the telemetry block run the one round
    body on both engines: fig2's (C=4, M=5) grid, a NaN poisoned into
    user (2, 4) at round 1 and zero-filled by the guard, swept on the
    single engine (``batch="map"``) and on a padded 2x2 mesh (4x6),
    gives bitwise-equal metrics, final state (``guard_trips`` and the
    telemetry block included) and telemetry trajectories; NaN compares
    equal to NaN, as `repro.obs.diff` counts it."""
    from conftest import run_forced_devices
    out = run_forced_devices(f"""
        import jax
        import numpy as np
        from repro.exec import ShardedSweepRunner
        from repro.ft import FaultPlan
        from repro.obs.diff import diff_trees
        from repro.sim import SweepRunner, get_scenario

        sc = get_scenario({scenario!r}).quick().replace(
            C=4, M=5, total_IT=4, eval_every=1, telemetry=True)
        kw = dict(seeds=[0], keep_state=True, guard="zero_fill",
                  faults=FaultPlan.parse("poison=nan@1:2:4"))
        ref = SweepRunner([sc], batch="map", **kw).run_scenario(sc)
        sh = ShardedSweepRunner([sc], mesh="2x2", **kw).run_scenario(sc)
        assert sh.exec_info["padded"] == "4x6", sh.exec_info
        assert ref.exec_info["guard_trips"] >= 1, ref.exec_info
        assert sh.exec_info["guard_trips"] == ref.exec_info["guard_trips"]
        res = diff_trees(ref.to_record(), sh.to_record())
        assert not res.errors and res.max_ulp == 0, (res.errors, res.ulps)
        eq = jax.tree.map(
            lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b),
                                             equal_nan=True)),
            ref.final_state, sh.final_state)
        assert jax.tree.all(eq), eq
        assert ref.telemetry and "telemetry" in ref.final_state
        print("ENGINES_OK")
    """, n_dev=4)
    assert "ENGINES_OK" in out


@pytest.mark.slow
def test_ft_cross_mesh_resume(tmp_path):
    """A checkpoint cut on a padded 2x4 mesh resumes on 1x1 bitwise
    (the PadPlan re-embedding is exact).  Slow tier; CI's resume-parity
    job runs the same legs via the CLI."""
    from conftest import run_forced_devices
    out = run_forced_devices(f"""
        import os, subprocess, sys, json
        args = [sys.executable, "-m", "repro.sim.sweep", "--scenarios",
                "fig2_iid", "--quick", "--seeds", "2", "--exec",
                "sharded"]
        env = dict(os.environ)
        d = {str(tmp_path)!r}
        r = subprocess.run(args + ["--mesh", "1x1", "--state-out",
                                   os.path.join(d, "ref.json")], env=env)
        assert r.returncode == 0
        r = subprocess.run(args + ["--mesh", "2x4", "--checkpoint",
                                   os.path.join(d, "ck"), "--inject",
                                   "crash_round=5"], env=env)
        assert r.returncode == 173, r.returncode
        r = subprocess.run(args + ["--mesh", "1x1", "--checkpoint",
                                   os.path.join(d, "ck"), "--resume",
                                   "--state-out",
                                   os.path.join(d, "res.json")], env=env)
        assert r.returncode == 0
        a = json.load(open(os.path.join(d, "ref.json")))
        b = json.load(open(os.path.join(d, "res.json")))
        assert a["scenarios"][0]["state"] == b["scenarios"][0]["state"]
        print("CROSS_MESH_OK")
    """)
    assert "CROSS_MESH_OK" in out
