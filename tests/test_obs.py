"""Tests for `repro.obs` — telemetry, run tracing, ULP parity audit.

Three contracts are pinned here:

1. **The off-switch is a bitwise no-op.**  ``telemetry=False`` (the
   default) must produce trajectories AND final states bitwise
   identical to a run of the same engine/driver with the feature
   enabled-but-off never having existed — and ``telemetry=True`` must
   never perturb them either (the diagnostics are fence-isolated
   consumers of already-materialized values; the x+0 discipline).
2. **The numbers mean what the docstrings say.**  `cluster_telemetry` /
   `is_telemetry` are checked against hand-computed numpy oracles on a
   1-cluster case, and the realized `attendance` trajectory of a
   bernoulli scenario must equal the host-side schedule oracle exactly.
3. **The tooling round-trips.**  Trace journals validate against their
   own schema; `repro.obs.diff` reproduces the CI parity verdicts
   (bitwise passes, 1-ULP tolerated, structural breaks fail); the
   trajectory document upgrade (v1 -> v2 + provenance) is lossless.
"""
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import bench_check  # noqa: E402
from benchmarks.report import trajectory_table  # noqa: E402
from repro.fed.clients import ClientPool, ParticipationSchedule  # noqa: E402
from repro.core.topology import uniform_topology  # noqa: E402
from repro.obs import diff as obs_diff  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.obs.telemetry import (EDGE_KEYS, IS_KEYS,  # noqa: E402
                                 TELEMETRY_KEYS, cluster_telemetry,
                                 is_telemetry, summarize, telemetry_init)
from repro.sim import get_scenario  # noqa: E402
from repro.sim.sweep import RECORD_KEYS, SweepRunner  # noqa: E402


# ---------------------------------------------------------------------------
# engine matrix: telemetry off is a bitwise no-op, on never perturbs
# ---------------------------------------------------------------------------

def _runner(engine, driver, telemetry):
    if engine == "sharded":
        from repro.exec import ShardedSweepRunner
        return ShardedSweepRunner(["fig2_iid"], seeds=2, quick=True,
                                  keep_state=True, mesh="1x1",
                                  driver=driver, telemetry=telemetry)
    return SweepRunner(["fig2_iid"], seeds=2, quick=True, keep_state=True,
                       batch="map", driver=driver, telemetry=telemetry)


@pytest.mark.parametrize("engine,driver", [
    ("single", "stepwise"), ("single", "chunked"),
    ("sharded", "stepwise"), ("sharded", "chunked"),
])
def test_telemetry_never_perturbs_results(engine, driver):
    off = _runner(engine, driver, False).run()[0]
    on = _runner(engine, driver, True).run()[0]

    # off: the record's telemetry slot exists but is null
    rec_off, rec_on = off.to_record(), on.to_record()
    assert tuple(sorted(rec_off)) == tuple(sorted(RECORD_KEYS))
    assert rec_off["telemetry"] is None
    assert sorted(rec_on["telemetry"]) == sorted(TELEMETRY_KEYS)

    # on: every trajectory bitwise identical to off (x+0 discipline)
    assert off.rounds == on.rounds
    assert rec_off["metrics"] == rec_on["metrics"]
    assert rec_off["final"] == rec_on["final"]

    # final model/opt state bitwise equal on the off-state's keys (the
    # on-state additionally carries the telemetry block)
    assert set(on.final_state) == set(off.final_state) | {"telemetry"}
    eq = jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        off.final_state, {k: v for k, v in on.final_state.items()
                          if k != "telemetry"})
    assert jax.tree.all(eq), eq

    # telemetry shape: one entry per eval per seed, scalars or [C]
    S, E = len(on.seeds), len(on.rounds)
    sc = on.scenario
    for k in TELEMETRY_KEYS:
        traj = rec_on["telemetry"][k]
        assert len(traj) == S and len(traj[0]) == E, k
        leaf = np.asarray(traj[0][0])
        assert leaf.shape in ((), (sc.C,)), (k, leaf.shape)
    assert all(v == 1.0
               for v in np.asarray(rec_on["telemetry"]["attendance"]).flat)


def test_telemetry_cross_engine_consistency():
    """The sharded engine's diagnostics are computed from gathered
    *real* (C, M) values, so they match the single engine's closely
    (same program modulo shard reduction order)."""
    a = _runner("single", "stepwise", True).run()[0].to_record()
    b = _runner("sharded", "chunked", True).run()[0].to_record()
    assert a["metrics"] == b["metrics"]
    for k in TELEMETRY_KEYS:
        np.testing.assert_allclose(
            np.asarray(a["telemetry"][k], np.float32),
            np.asarray(b["telemetry"][k], np.float32), rtol=1e-6, err_msg=k)


def test_conventional_mode_zeroes_is_block():
    sc = get_scenario("fig2_iid_conventional")
    r = SweepRunner([sc], seeds=1, quick=True, batch="map",
                    telemetry=True).run()[0]
    tele = r.to_record()["telemetry"]
    for k in IS_KEYS:
        assert np.all(np.asarray(tele[k]) == 0.0), k
    for k in ("snr", "rx_power"):
        assert np.all(np.asarray(tele[k]) > 0.0), k


# ---------------------------------------------------------------------------
# numpy oracles for the diagnostics themselves
# ---------------------------------------------------------------------------

def _hand_case():
    topo = uniform_topology(C=1, M=2, K=4, K_ps=4, sigma_z2=2.0)
    n = 3  # N symbols -> 2N reals
    flat = np.arange(1, 1 + 2 * n * 2, dtype=np.float32).reshape(1, 2, 2 * n)
    est = np.linspace(-1.0, 1.0, 2 * n, dtype=np.float32).reshape(1, 2 * n)
    return topo, flat, est, n


def test_cluster_telemetry_matches_numpy_oracle():
    topo, flat, est, N = _hand_case()
    out = {k: np.asarray(v) for k, v in
           cluster_telemetry(flat, est, None, topo, 2.5).items()}
    assert sorted(out) == sorted(EDGE_KEYS)

    P = np.float32(2.5)
    E = (flat.astype(np.float64) ** 2).sum(-1)              # [1, 2]
    beta = topo.beta_own
    rx = P ** 2 * (beta * E).sum(-1) / N
    np.testing.assert_allclose(out["rx_power"], rx, rtol=1e-6)
    np.testing.assert_allclose(out["snr"], rx / topo.sigma_z2, rtol=1e-6)
    np.testing.assert_allclose(
        out["noise_floor"],
        topo.sigma_z2 / (P ** 2 * topo.sigma_h2 * topo.beta_bar_c * topo.K),
        rtol=1e-6)
    np.testing.assert_allclose(
        out["symbol_energy_edge"], P ** 2 * E.mean(-1) / N, rtol=1e-6)
    pre = np.linalg.norm(flat.mean(axis=1), axis=-1)
    post = np.linalg.norm(est, axis=-1)
    np.testing.assert_allclose(out["grad_norm_pre"], pre, rtol=1e-6)
    np.testing.assert_allclose(out["grad_norm_post"], post, rtol=1e-6)
    np.testing.assert_allclose(out["grad_ratio"], post / pre, rtol=1e-6)
    assert out["attendance"] == 1.0

    # a claimed mask feeds the attendance fraction; zero pre-norm
    # short-circuits the ratio instead of dividing by zero
    half = cluster_telemetry(flat, est, np.array([[1.0, 0.0]], np.float32),
                             topo, 2.5)
    assert float(half["attendance"]) == 0.5
    zero = cluster_telemetry(np.zeros_like(flat), est, None, topo, 2.5)
    assert float(np.asarray(zero["grad_ratio"])[0]) == 0.0


def test_is_telemetry_matches_numpy_oracle():
    topo, _, est, N = _hand_case()
    out = is_telemetry(est, topo, 1.5)
    P = np.float32(1.5)
    E = (est.astype(np.float64) ** 2).sum(-1)               # [1]
    np.testing.assert_allclose(np.asarray(out["symbol_energy_is"]),
                               P ** 2 * E.mean() / N, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(out["snr_is"]),
        P ** 2 * (topo.beta_is * E).sum() / (N * topo.sigma_z2), rtol=1e-6)


def test_summarize_and_init_structure():
    topo, flat, est, _ = _hand_case()
    tele = {**cluster_telemetry(flat, est, None, topo, 1.0),
            **is_telemetry(est, topo, 1.0)}
    s = summarize(tele)
    assert sorted(s) == sorted(TELEMETRY_KEYS)
    assert all(isinstance(v, float) for v in s.values())
    init = telemetry_init(C=1)
    assert sorted(init) == sorted(TELEMETRY_KEYS)
    assert jax.tree.structure(init) == jax.tree.structure(
        jax.tree.map(lambda x: x, tele))


def test_attendance_matches_participation_schedule_oracle():
    """The in-program attendance diagnostic equals the host schedule's
    realized fraction, eval round by eval round, exactly."""
    sc = get_scenario("fig2_drop50").quick()
    r = SweepRunner([sc], seeds=1, quick=False, batch="map",
                    telemetry=True).run()[0]
    sched = sc.participation_schedule()
    got = [float(np.asarray(a)) for a in r.to_record()["telemetry"]
           ["attendance"][0]]
    want = [float(sched.attendance_fraction(rd - 1, sc.C, sc.M))
            for rd in r.rounds]
    assert got == want, (got, want)
    assert any(v < 1.0 for v in got)  # the drop actually happened


# ---------------------------------------------------------------------------
# host-side attendance accounting (repro.fed.clients)
# ---------------------------------------------------------------------------

def test_attendance_fraction_helper():
    full = ParticipationSchedule(kind="full")
    assert float(full.attendance_fraction(0, 2, 3)) == 1.0
    bern = ParticipationSchedule(kind="bernoulli", rate=0.5, seed=7)
    for t in range(3):
        assert float(bern.attendance_fraction(t, 4, 5)) == float(
            np.mean(np.asarray(bern.present(t, 4, 5))))


def test_client_pool_attendance_fractions():
    C, M, n = 2, 2, 4
    pool = ClientPool(X=np.zeros((C, M, n, 2), np.float32),
                      Y=np.zeros((C, M, n), np.int32))
    # before any round: vacuous full attendance
    assert pool.rounds_seen == 0
    assert (pool.attendance_fractions() == 1.0).all()
    pool.mark_round()                                   # everyone
    pool.mark_round(np.array([[1, 0], [1, 1]], np.float32))
    assert pool.rounds_seen == 2
    np.testing.assert_allclose(pool.attendance_fractions(),
                               [[1.0, 0.5], [1.0, 1.0]])
    with pytest.raises(ValueError, match="mask shape"):
        pool.mark_round(np.ones((3, 3)))
    assert pool.rounds_seen == 2  # a rejected mask must not count


# ---------------------------------------------------------------------------
# repro.obs.diff — the ULP parity audit
# ---------------------------------------------------------------------------

def test_ulp_distance():
    one = np.float32(1.0)
    assert int(obs_diff.ulp_distance(one, one)) == 0
    assert int(obs_diff.ulp_distance(one, np.nextafter(one, 2))) == 1
    assert int(obs_diff.ulp_distance(one, np.nextafter(one, 0))) == 1
    assert int(obs_diff.ulp_distance(-one, np.nextafter(-one, 0))) == 1
    assert int(obs_diff.ulp_distance(0.0, -0.0)) == 0
    assert int(obs_diff.ulp_distance(float("nan"), float("nan"))) == 0
    # crossing zero counts representable values on both sides
    tiny = float(np.nextafter(np.float32(0), 1))
    assert int(obs_diff.ulp_distance(tiny, -tiny)) == 2


def test_ulp_distance_f64_path():
    """Regression: a float64 pair differing below f32 precision used to
    collapse to ULP 0 under an unconditional f32 cast — the f64 path
    (int64 view, same sign-magnitude ordering) must report it nonzero,
    while pairs of exactly-f32-representable values keep their f32 ULP
    count (the CI residue gates rely on --max-ulp 1 meaning 1 f32 ULP
    there)."""
    # sub-f32-ULP f64 pair: nonzero, and exact on the f64 grid
    a, b = 1.0, 1.0 + 2.0 ** -40
    assert int(obs_diff.ulp_distance(a, b)) == 2 ** 12
    assert int(obs_diff.ulp_distance(1.0, np.nextafter(1.0, 2.0))) == 1
    # f32-exact values stay on the f32 grid: adjacent f32s are 1 ULP,
    # not the ~2^29 f64 ULPs an unconditional f64 view would report
    x = float(np.float32(0.5))
    y = float(np.nextafter(np.float32(0.5), np.float32(1)))
    assert int(obs_diff.ulp_distance(x, y)) == 1
    # mixed lists select the grid elementwise
    d = obs_diff.ulp_distance([x, 1.0], [y, 1.0 + 2.0 ** -40])
    assert d.tolist() == [1, 2 ** 12]
    # f64 specials keep the f32 path's conventions
    assert int(obs_diff.ulp_distance(1e-300, 1e-300)) == 0
    assert int(obs_diff.ulp_distance(float("nan"),
                                     float("nan"))) == 0
    assert int(obs_diff.ulp_distance(0.0, -0.0)) == 0
    assert int(obs_diff.ulp_distance(1e308, -1e308)) > 0  # no overflow
    # and the gate end-to-end: the sub-ULP pair fails --max-ulp 0
    res = obs_diff.diff_trees({"p": a}, {"p": b})
    assert res.max_ulp > 0 and not res.verdict(0)


def _doc(loss=0.5, seconds=1.0, extra=None):
    d = {"schema": "x/v1", "quick": True,
         "scenarios": [{"scenario": {"name": "sc", "tau": 2},
                        "rounds": [2, 4],
                        "metrics": {"loss": [[loss, 0.25]]},
                        "seconds": seconds}]}
    if extra:
        d["scenarios"][0].update(extra)
    return d


def test_diff_trees_bitwise_and_ulp_verdicts():
    res = obs_diff.diff_trees(_doc(), _doc(seconds=9.0))  # ignored key
    assert not res.errors and res.max_ulp == 0
    assert res.verdict(0)

    bumped = float(np.nextafter(np.float32(0.5), 1))
    res = obs_diff.diff_trees(_doc(), _doc(loss=bumped))
    assert not res.errors and res.max_ulp == 1
    assert not res.verdict(0) and res.verdict(1)
    (path,) = [p for p, u in res.ulps.items() if u > 0]
    assert path.endswith("metrics.loss[0]")


def test_diff_trees_structural_mismatches():
    a, b = _doc(), _doc()
    b["scenarios"][0]["rounds"] = [2]                   # length break
    b["scenarios"][0]["scenario"]["name"] = "other"     # string break
    res = obs_diff.diff_trees(a, b)
    assert len(res.errors) == 2 and not res.verdict(10)

    res = obs_diff.diff_trees(_doc(), _doc(extra={"telemetry": None}))
    assert any("missing" in e for e in res.errors)

    # int paths are exact: a 1-off integer is structural, not 1 ULP
    res = obs_diff.diff_trees({"n": [1, 2]}, {"n": [1, 3]})
    assert any("integer mismatch" in e for e in res.errors)


def test_diff_cli_reproduces_ci_verdict(tmp_path, capsys):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_doc()))
    b.write_text(json.dumps(_doc(loss=float(
        np.nextafter(np.float32(0.5), 1)))))
    assert obs_diff.main([str(a), str(a)]) == 0
    assert obs_diff.main([str(a), str(b)]) == 1          # bitwise gate
    assert obs_diff.main([str(a), str(b), "--max-ulp", "1"]) == 0
    out = capsys.readouterr().out
    assert "max ULP 1" in out and "PASS" in out
    # --ignore widens the skip set; --no-default-ignore narrows it
    assert obs_diff.main([str(a), str(b), "--ignore", "metrics"]) == 0
    c = tmp_path / "c.json"
    c.write_text(json.dumps(_doc(seconds=2.0)))
    assert obs_diff.main([str(a), str(c), "--no-default-ignore"]) == 1


# ---------------------------------------------------------------------------
# repro.obs.trace — the JSONL run journal
# ---------------------------------------------------------------------------

def test_trace_writer_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs_trace.TraceWriter(path) as w:
        w.emit("scenario_start", scenario="sc", seeds=1, rounds=4,
               driver="stepwise", telemetry=False, exec_info={})
        w.emit("window", scenario="sc", round=2, rounds=2, seconds=0.1)
        w.emit("scenario_end", scenario="sc", seconds=0.2,
               drive_seconds=0.1, dispatches=5, n_traces=1,
               final_acc_mean=0.5)
    counts, errors = obs_trace.validate_trace(path)
    assert errors == [], errors
    assert counts == {"run_start": 1, "scenario_start": 1, "window": 1,
                      "scenario_end": 1, "run_end": 1}
    first = json.loads(open(path).read().splitlines()[0])
    assert first["schema"] == obs_trace.SCHEMA_VERSION
    assert first["jax_version"] == jax.__version__
    with pytest.raises(ValueError, match="unknown trace event"):
        obs_trace.TraceWriter(str(tmp_path / "x.jsonl")).emit("explode")


def test_trace_validator_rejects_bad_journals(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text("not json at all\n")
    counts, errors = obs_trace.validate_trace(str(bad))
    assert errors and obs_trace.main([str(bad)]) == 1

    # a crashed run: run_start only, no run_end
    crash = tmp_path / "crash.jsonl"
    w = obs_trace.TraceWriter(str(crash))
    w.emit("scenario_start", scenario="sc")
    w._f.flush()
    _, errors = obs_trace.validate_trace(str(crash))
    assert any("run_end" in e for e in errors)
    assert any("unbalanced" in e for e in errors)
    w.close()


def test_sweep_writes_valid_trace(tmp_path):
    """End to end: a real (quick) sweep with --telemetry journaling
    through both drivers produces a schema-valid trace."""
    path = str(tmp_path / "sweep.jsonl")
    with obs_trace.TraceWriter(path) as w:
        for driver in ("stepwise", "chunked"):
            SweepRunner(["fig2_iid"], seeds=1, quick=True, batch="map",
                        driver=driver, telemetry=True, trace=w).run()
    counts, errors = obs_trace.validate_trace(path)
    assert errors == [], errors
    assert counts["scenario_start"] == counts["scenario_end"] == 2
    assert counts["window"] >= 2 and counts["telemetry"] >= 2
    assert counts["compile"] >= 1
    events = [json.loads(line) for line in open(path)]
    chunk_windows = [e for e in events if e["event"] == "window"
                     and e.get("enqueue_only")]
    assert chunk_windows, "chunked windows must be flagged enqueue_only"
    assert obs_trace.main([path]) == 0


# ---------------------------------------------------------------------------
# trajectory provenance (benchmarks/bench_check.py v2) + report table
# ---------------------------------------------------------------------------

def _bench_rec():
    """A fresh BENCH_sweep record, as bench_doc emits it."""
    return {"scenario": "sc", "rounds_per_sec": 10.0, "driver": "stepwise",
            "dispatches": 12, "exec": {"name": "single", "mesh": None,
                                       "driver": "stepwise"}}


def _traj_rec():
    """A trajectory-entry record, as append_trajectory stores it."""
    return {"scenario": "sc", "exec": "single", "driver": "stepwise",
            "mesh": None, "rounds_per_sec": 10.0, "dispatches": 12}


def test_trajectory_v2_provenance_and_v1_upgrade(tmp_path):
    path = str(tmp_path / "traj.json")
    # seed a v1 document (as an old CI cache would restore it)
    json.dump({"schema": "repro.bench.trajectory/v1",
               "runs": [{"run_id": "old", "timestamp": "t0",
                         "passed": True, "records": []}]},
              open(path, "w"))
    bench_check.append_trajectory(path, [_bench_rec()], True, "new", "t1")
    doc = json.load(open(path))
    assert doc["schema"] == bench_check.TRAJECTORY_SCHEMA  # upgraded
    assert [r["run_id"] for r in doc["runs"]] == ["old", "new"]
    prov = doc["runs"][1]["provenance"]
    for k in ("git_sha", "jax_version", "platform", "python"):
        assert prov[k], k
    assert "provenance" not in doc["runs"][0]  # v1 entries untouched

    # still refuses non-trajectory targets
    other = tmp_path / "sweep.json"
    other.write_text(json.dumps({"schema": "repro.bench.sweep/v1"}))
    with pytest.raises(SystemExit, match="refusing to overwrite"):
        bench_check.append_trajectory(str(other), [], True, "x", "t")


def test_trajectory_report_table(tmp_path):
    doc = {"schema": bench_check.TRAJECTORY_SCHEMA, "runs": [
        {"run_id": "old", "timestamp": "t0", "passed": True,
         "records": [_traj_rec()]},                       # v1-style entry
        {"run_id": "new", "timestamp": "t1", "passed": True,
         "provenance": {"git_sha": "abcdef0123456789", "jax_version":
                        "0.9.0", "device_count": 8, "platform": "x"},
         "records": [_traj_rec()]},
    ]}
    table = trajectory_table(doc)
    assert "### sc — single/stepwise" in table
    assert "| rounds/sec |" in table
    assert "abcdef012" in table and "abcdef0123" not in table  # sha[:9]
    assert "| old | t0 | — | — | — | 10.00 | 12 |" in table
    assert trajectory_table({"runs": []}).startswith("(empty")
