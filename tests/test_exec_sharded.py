"""Sharded execution engine (repro.exec) tests.

The engine's contract is *bitwise mesh-invariance*: for a fixed
scenario and seed, a sweep on a 1x1 mesh and on a 2x4 mesh produce
identical trajectories and identical final states (training, both OTA
hops, and power accounting included).  Multi-device runs need forced
host devices, so those checks run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process must keep seeing 1 device).
"""
import pytest
from conftest import FakeMesh
from conftest import run_forced_devices as _run

from repro.exec import host_device_recipe, make_device_mesh, parse_mesh


# ---------------------------------------------------------------------------
# mesh plumbing (single device, in-process)
# ---------------------------------------------------------------------------

def test_parse_mesh():
    assert parse_mesh("2x4") == (2, 4)
    assert parse_mesh("1X1") == (1, 1)
    assert parse_mesh((4, 2)) == (4, 2)
    for bad in ("2x", "x4", "0x2", "2x4x2", "abc"):
        with pytest.raises(ValueError):
            parse_mesh(bad)


def test_make_device_mesh_single_and_oversubscribed():
    mesh = make_device_mesh("1x1")
    assert mesh.axis_names == ("cluster", "user")
    assert mesh.devices.shape == (1, 1)
    with pytest.raises(ValueError, match="devices"):
        make_device_mesh("64x64")
    assert "xla_force_host_platform_device_count=8" in host_device_recipe(8)


def test_engine_registry():
    from repro.exec import ShardedSweepRunner, make_runner
    from repro.sim import SweepRunner, get_scenario
    sc = get_scenario("scale_u256")
    r = make_runner("single", [sc], seeds=1)
    assert type(r) is SweepRunner
    r = make_runner("sharded", [sc], seeds=1, mesh="1x1")
    assert isinstance(r, ShardedSweepRunner) and r.batch == "map"
    with pytest.raises(ValueError, match="unknown execution engine"):
        make_runner("turbo", [sc])


def test_mesh_divisibility_validation():
    from repro.exec import validate_mesh_for

    assert validate_mesh_for(make_device_mesh("1x1"), 4, 5) == (4, 5)
    assert validate_mesh_for(FakeMesh(2, 4), 4, 64) == (2, 16)
    with pytest.raises(ValueError, match="does not divide"):
        validate_mesh_for(FakeMesh(2, 4), 4, 5)


# ---------------------------------------------------------------------------
# bitwise mesh-invariance (subprocess, 8 forced host devices)
# ---------------------------------------------------------------------------

def test_scale_u256_sharded_1x1_vs_2x4_bitwise_and_seed_slice():
    """The acceptance contract: scale_u256 swept on a 2x4 mesh is
    bitwise identical (metrics AND final params/optimizer state) to the
    single-device (1x1) run, and a single-seed sharded run equals its
    slice of the seed batch."""
    _run("""
    import jax
    import numpy as np
    from repro.exec import ShardedSweepRunner
    from repro.sim import get_scenario
    from repro.sim.sweep import RECORD_KEYS

    sc = get_scenario("scale_u256").replace(
        total_IT=2, n_train=512, n_test=128, K=8, K_ps=8)
    r1 = ShardedSweepRunner([sc], seeds=[0, 1], mesh="1x1",
                            keep_state=True).run_scenario(sc)
    r2 = ShardedSweepRunner([sc], seeds=[0, 1], mesh="2x4",
                            keep_state=True).run_scenario(sc)
    assert r1.acc == r2.acc, (r1.acc, r2.acc)
    assert r1.loss == r2.loss
    assert r1.edge_power == r2.edge_power
    assert r1.is_power == r2.is_power
    eq = jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        r1.final_state, r2.final_state)
    assert jax.tree.all(eq), eq

    # seed-slice: [1] alone == slice 1 of the [0, 1] batch (map mode)
    r3 = ShardedSweepRunner([sc], seeds=[1], mesh="2x4",
                            keep_state=True).run_scenario(sc)
    assert r3.acc[0] == r2.acc[1]
    assert r3.edge_power[0] == r2.edge_power[1]

    # records carry the exec metadata and keep the pinned schema
    rec = r2.to_record()
    assert tuple(sorted(rec)) == tuple(sorted(RECORD_KEYS))
    ex = dict(rec["exec"])
    assert ex.pop("drive_seconds") > 0
    assert ex.pop("peak_symbol_bytes") > 0
    assert ex == {"name": "sharded", "mesh": "2x4", "device_count": 8,
                  "batch": "map", "driver": "stepwise", "padded": None,
                  "combine": "gathered", "dispatches": 2 * 2 + 2,
                  "warmup": False, "label_lookup": "dense"}
    print("OK")
    """)


# ---------------------------------------------------------------------------
# combine=u_sharded: the partial fused combine (subprocess, 8 devices)
# ---------------------------------------------------------------------------

def test_u_sharded_combine_bitwise_vs_gathered_and_single():
    """The tentpole contract: `combine=u_sharded` — per-shard partial
    kernels + the pinned-order cross-shard fold — is bitwise equal
    (metrics AND final state) to the gathered path, to the single
    engine, and to itself on every mesh shape, on both drivers."""
    _run("""
    import jax
    import numpy as np
    from repro.exec import ShardedSweepRunner
    from repro.sim import SweepRunner, get_scenario

    sc = get_scenario("scale_u256").replace(
        total_IT=2, n_train=512, n_test=128, K=8, K_ps=8)

    def bitwise(a, b, tag):
        assert a.acc == b.acc, (tag, a.acc, b.acc)
        assert a.loss == b.loss, tag
        assert a.edge_power == b.edge_power, tag
        assert a.is_power == b.is_power, tag
        eq = jax.tree.map(
            lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()),
            a.final_state, b.final_state)
        assert jax.tree.all(eq), (tag, eq)

    single = SweepRunner([sc], seeds=[0, 1], batch="map",
                         keep_state=True).run_scenario(sc)
    gathered = ShardedSweepRunner([sc], seeds=[0, 1], mesh="2x4",
                                  keep_state=True).run_scenario(sc)
    for mesh, driver in (("1x1", "stepwise"), ("2x4", "stepwise"),
                         ("8x1", "chunked")):
        u = ShardedSweepRunner([sc], seeds=[0, 1], mesh=mesh,
                               driver=driver, keep_state=True,
                               combine="u_sharded").run_scenario(sc)
        bitwise(u, single, ("single", mesh, driver))
        bitwise(u, gathered, ("gathered", mesh, driver))
        assert u.exec_info["combine"] == "u_sharded"

    # the memory contract, on the tier it is FOR: at scale_u16384
    # (M = 1024) the u_sharded per-device peak symbol bytes fall 4x
    # under the gathered full block.  (At this test's M = 64 the
    # K-resolved partial accumulators legitimately dominate the tiny
    # symbol tile — the partial combine is a large-M lever, which is
    # why scale_u65536 is registered u_sharded-only.)  Sized from the
    # recorded estimate, no 16384-user sweep needed.
    sc16 = get_scenario("scale_u16384")
    topo16 = sc16.make_topology()
    g8 = ShardedSweepRunner([sc16], seeds=[0], mesh="8x1")
    u8 = ShardedSweepRunner([sc16], seeds=[0], mesh="8x1",
                            combine="u_sharded")
    gb = g8._exec_info(topo16, two_n=7850)["peak_symbol_bytes"]
    ub = u8._exec_info(topo16, two_n=7850)["peak_symbol_bytes"]
    assert gb >= 4 * ub, (gb, ub)
    print("OK")
    """)


def test_u_sharded_combine_padded_mesh_and_participation():
    """u_sharded on a mesh that does not divide (C, M) — padded
    clusters' trailing partial blocks are dropped before the fold —
    and under a Bernoulli participation mask, both bitwise equal to
    the gathered path and the single engine."""
    _run("""
    import jax
    import numpy as np
    from repro.exec import ShardedSweepRunner
    from repro.sim import SweepRunner, get_scenario

    base = get_scenario("scale_u256").replace(
        total_IT=2, n_train=512, n_test=128, K=8, K_ps=8)
    part = base.replace(participation="bernoulli",
                        participation_rate=0.75)
    for sc in (base, part):
        single = SweepRunner([sc], seeds=[0], batch="map",
                             keep_state=True).run_scenario(sc)
        for mesh in ("3x2", "2x4"):
            u = ShardedSweepRunner([sc], seeds=[0], mesh=mesh,
                                   keep_state=True,
                                   combine="u_sharded").run_scenario(sc)
            assert u.acc == single.acc, (sc.name, mesh)
            assert u.edge_power == single.edge_power, (sc.name, mesh)
            assert u.is_power == single.is_power, (sc.name, mesh)
            eq = jax.tree.map(
                lambda x, y: bool(
                    (np.asarray(x) == np.asarray(y)).all()),
                single.final_state, u.final_state)
            assert jax.tree.all(eq), (sc.name, mesh, eq)
    print("OK")
    """)


def test_combine_validation():
    from repro.exec import ShardedSweepRunner, make_runner
    from repro.sim import get_scenario
    sc = get_scenario("scale_u256")
    with pytest.raises(ValueError, match="unknown combine"):
        ShardedSweepRunner([sc], combine="psum")
    with pytest.raises(ValueError, match="requires the sharded engine"):
        make_runner("single", [sc], combine="u_sharded")
    r = make_runner("sharded", [sc], mesh="1x1", combine="u_sharded")
    assert r.combine == "u_sharded"
    assert r._exec_info()["combine"] == "u_sharded"


def test_nonfused_backends_and_conventional_mesh_invariant():
    """fig2-family scenarios (equivalent/reference backends, the
    conventional baseline and the error-free mode) run unmodified on a
    mesh and reproduce the 1x1 trajectories bitwise."""
    _run("""
    from repro.exec import ShardedSweepRunner
    from repro.sim import get_scenario

    names = ("fig2_iid", "fig2_iid_conventional", "fig2_iid_ideal")
    for name in names:
        sc = get_scenario(name).quick().replace(total_IT=2, eval_every=1)
        a = ShardedSweepRunner([sc], seeds=[0], mesh="1x1").run_scenario(sc)
        b = ShardedSweepRunner([sc], seeds=[0], mesh="2x2").run_scenario(sc)
        assert a.acc == b.acc, (name, a.acc, b.acc)
        assert a.edge_power == b.edge_power, name
        assert a.is_power == b.is_power, name
    sc = get_scenario("fig2_iid").quick().replace(
        total_IT=2, eval_every=1, ota_mode="faithful")
    a = ShardedSweepRunner([sc], seeds=[0], mesh="1x1").run_scenario(sc)
    b = ShardedSweepRunner([sc], seeds=[0], mesh="2x2").run_scenario(sc)
    assert a.acc == b.acc
    print("OK")
    """)


def test_chunked_driver_sharded_bitwise_and_mesh_invariant():
    """The chunked driver on the sharded engine (the round scan runs
    *inside* the shard_map): bitwise equal to the stepwise sharded run
    — metrics and final state — at a non-divisible tail window
    (T=3, eval_every=2), and still bitwise invariant to the mesh."""
    _run("""
    import jax
    import numpy as np
    from repro.exec import ShardedSweepRunner
    from repro.sim import get_scenario

    sc = get_scenario("scale_u256").replace(
        total_IT=3, n_train=512, n_test=128, K=8, K_ps=8, eval_every=2)
    step = ShardedSweepRunner([sc], seeds=[0, 1], mesh="2x4",
                              keep_state=True).run_scenario(sc)
    chunk = ShardedSweepRunner([sc], seeds=[0, 1], mesh="2x4",
                               driver="chunked",
                               keep_state=True).run_scenario(sc)
    assert chunk.rounds == step.rounds == [1, 3]
    assert chunk.acc == step.acc, (chunk.acc, step.acc)
    assert chunk.loss == step.loss
    assert chunk.edge_power == step.edge_power
    assert chunk.is_power == step.is_power
    eq = jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        step.final_state, chunk.final_state)
    assert jax.tree.all(eq), eq
    assert chunk.exec_info["dispatches"] == 2      # one per eval window

    # chunked retains the engine's bitwise mesh-invariance
    one = ShardedSweepRunner([sc], seeds=[0, 1], mesh="1x1",
                             driver="chunked").run_scenario(sc)
    assert one.acc == chunk.acc
    assert one.edge_power == chunk.edge_power
    print("OK")
    """)


def test_vmap_seeds_over_sharded_round():
    """Seed batching lifts over the sharded round exactly as
    `vmap_seeds` lifts an OTA hop: vmapping the shard_map'd round over
    stacked (state, key) matches per-seed calls."""
    _run("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import aggregation as agg
    from repro.core.whfl import init_round_state
    from repro.exec import make_device_mesh, make_sharded_round_fn
    from repro.nn.core import split_params
    from repro.optim import sgd
    from repro.sim import get_scenario

    sc = get_scenario("scale_u256").replace(
        total_IT=1, n_train=512, n_test=64, K=8, K_ps=8)
    init_fn, _, loss_fn = sc.task_fns()
    X, Y, _, _ = sc.make_data()
    topo = sc.make_topology()
    opt = sgd(sc.lr)
    params = [split_params(init_fn(jax.random.PRNGKey(s)))[0]
              for s in (0, 1)]
    spec = agg.make_flat_spec(params[0])
    mesh = make_device_mesh("2x4")
    round_fn = make_sharded_round_fn(loss_fn, opt, topo, sc.whfl_config(),
                                     spec, X, Y, mesh)
    states = [init_round_state(p, opt, topo.C, topo.M) for p in params]
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    keys = jnp.stack([jax.random.PRNGKey(s + 1) for s in (0, 1)])
    out = jax.jit(jax.vmap(round_fn, in_axes=(0, 0, None, None)))(
        state, keys, 1.0, 20.0)
    for s in (0, 1):
        solo = jax.jit(round_fn)(states[s], keys[s], 1.0, 20.0)
        for a, b in zip(jax.tree.leaves(solo["theta"]),
                        jax.tree.leaves(
                            jax.tree.map(lambda x: x[s], out["theta"]))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-5)
    print("OK")
    """)
