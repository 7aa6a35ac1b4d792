"""Distributed W-HFL (shard_map) tests.

These need >1 host device, so they run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count set (the main pytest
process must keep seeing 1 device per the assignment brief).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def _run(script: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_ideal_aggregation_is_exact_mean():
    _run("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
    from repro.core.dist import OTADistConfig, whfl_aggregate, uniform_geom
    from repro.launch.mesh import refine_mesh
    import jax as j

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rmesh = refine_mesh(mesh, users_per_cluster=2)   # 2 clusters x 2 users
    geom = uniform_geom(C=2, M=2)
    cfg = OTADistConfig(mode="ideal")

    def f(x):
        est = whfl_aggregate({"w": x}, geom, jnp.zeros((2,), jnp.uint32),
                             1.0, 20.0, cfg)
        return est["w"]

    from repro.sharding import shard_map
    g = shard_map(f, mesh=rmesh,
                  in_specs=P(("pod", "cluster", "user")), out_specs=P(),
                  axis_names={"pod", "cluster", "user"}, check_vma=False)
    x = jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6)
    out = jax.jit(g)(x)
    np.testing.assert_allclose(np.asarray(out)[0], x.mean(0), rtol=1e-6)
    print("OK")
    """)


def test_equivalent_aggregation_unbiased_and_fused_matches():
    _run("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.dist import OTADistConfig, whfl_aggregate, uniform_geom
    from repro.launch.mesh import refine_mesh
    from repro.sharding import shard_map

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rmesh = refine_mesh(mesh, users_per_cluster=2)
    geom = uniform_geom(C=2, M=2, K=64, K_ps=64, sigma_z2=0.5)

    def agg(cfg):
        def f(x, key):
            est = whfl_aggregate({"w": x}, geom, key, 1.0, 20.0, cfg)
            return est["w"]
        # fully manual (model axis too): the body never touches the
        # model axis, and partial-auto cannot lower on older jax/XLA:CPU
        return jax.jit(shard_map(
            f, mesh=rmesh,
            in_specs=(P(("pod", "cluster", "user")), P()), out_specs=P(),
            check_vma=False))

    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
    tgt = np.asarray(x.mean(0))
    for name, cfg in [
        ("structural", OTADistConfig(mode="equivalent")),
        ("struct-scalar", OTADistConfig(mode="equivalent",
                                        per_element_interference=False)),
        ("fused", OTADistConfig(mode="equivalent", fused=True)),
    ]:
        f = agg(cfg)
        ests = np.stack([np.asarray(f(x, jax.random.PRNGKey(i))[0])
                         for i in range(300)])
        bias = np.abs(ests.mean(0) - tgt).mean()
        std = ests.std(0).mean()
        assert std > 1e-4, (name, std)          # channel noise present
        assert bias < 5 * std / np.sqrt(300) + 1e-3, (name, bias, std)
        print(name, "bias", bias, "std", std)
    print("OK")
    """)


def test_train_step_runs_and_learns():
    _run("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_production_mesh
    from repro.launch.train import TrainConfig, build_train_step
    from repro.core.dist import OTADistConfig

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen2-0.5b").reduced()
    shape = InputShape("tiny", 64, 8, "train")
    tcfg = TrainConfig(tau=1, I=1, users_per_cluster=2, eta_local=0.0,
                       outer="adamw", outer_lr=2e-3,
                       ota=OTADistConfig(mode="ideal"))
    # eta_local=0 would kill learning; use tau=1 path with eta folded in
    tcfg = TrainConfig(tau=1, I=1, users_per_cluster=2, eta_local=1.0,
                       outer="adamw", outer_lr=2e-3,
                       ota=OTADistConfig(mode="ideal"))
    step, init_fn, shardings_fn, rmesh = build_train_step(
        cfg, shape, mesh, tcfg)
    state, axes = init_fn(jax.random.PRNGKey(0))
    sh = shardings_fn(axes)
    jstep = jax.jit(step, in_shardings=(sh["state"], sh["batch"], sh["key"]),
                    out_shardings=(sh["state"], sh["metrics"]))
    kb = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(kb, (8, 64), 0, cfg.vocab),
        "labels": jax.random.randint(kb, (8, 64), 0, cfg.vocab),
    }
    losses = []
    for i in range(8):
        state, m = jstep(state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
        assert float(m["edge_power"]) >= 0
    assert losses[-1] < losses[0], losses   # memorizes the fixed batch
    print("losses", losses)
    print("OK")
    """)


def test_local_sgd_tau_I_path():
    _run("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.train import TrainConfig, build_train_step
    from repro.core.dist import OTADistConfig

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen2-0.5b").reduced()
    shape = InputShape("tiny", 32, 16, "train")
    kb = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(kb, (16, 32), 0, cfg.vocab),
        "labels": jax.random.randint(kb, (16, 32), 0, cfg.vocab),
    }

    def run(ota, rounds):
        tcfg = TrainConfig(tau=2, I=2, users_per_cluster=2, eta_local=5e-3,
                           outer="add", ota=ota)
        step, init_fn, shardings_fn, _ = build_train_step(
            cfg, shape, mesh, tcfg)
        state, axes = init_fn(jax.random.PRNGKey(0))
        sh = shardings_fn(axes)
        jstep = jax.jit(step,
                        in_shardings=(sh["state"], sh["batch"], sh["key"]),
                        out_shardings=(sh["state"], sh["metrics"]))
        losses = []
        for i in range(rounds):
            state, m = jstep(state, batch, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
            assert np.isfinite(losses[-1])
        return losses

    # error-free channel: the I x tau local-SGD protocol must learn
    losses = run(OTADistConfig(mode="ideal"), 6)
    assert losses[-1] < losses[0], losses
    # equivalent channel with a quiet radio (K=1024 antennas): finite +
    # still learning despite channel perturbations
    from repro.core.dist import uniform_geom
    quiet = uniform_geom(C=2, M=2, K=1024, K_ps=1024, sigma_z2=1e-3)
    tcfg2 = TrainConfig(tau=2, I=2, users_per_cluster=2, eta_local=5e-3,
                        outer="add", ota=OTADistConfig(mode="equivalent"),
                        geom=quiet)
    step, init_fn, shardings_fn, _ = build_train_step(
        cfg, shape, mesh, tcfg2)
    state, axes = init_fn(jax.random.PRNGKey(0))
    sh = shardings_fn(axes)
    jstep = jax.jit(step, in_shardings=(sh["state"], sh["batch"], sh["key"]),
                    out_shardings=(sh["state"], sh["metrics"]))
    losses2 = []
    for i in range(6):
        state, m = jstep(state, batch, jax.random.PRNGKey(i))
        losses2.append(float(m["loss"]))
        assert np.isfinite(losses2[-1])
    assert losses2[-1] < losses2[0], losses2
    print("losses", losses, losses2)
    print("OK")
    """)


@pytest.mark.slow
def test_fused_fsdp_train_step():
    _run("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.train import TrainConfig, build_fused_train_step
    from repro.core.dist import OTADistConfig

    mesh = jax.make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen2-0.5b").reduced()
    shape = InputShape("tiny", 64, 8, "train")
    tcfg = TrainConfig(tau=1, I=1, users_per_cluster=2, eta_local=1.0,
                       outer="adamw", outer_lr=2e-3, fsdp=True,
                       ota=OTADistConfig(mode="equivalent",
                                         tx_power_proxy=1e-4))
    step, init_fn, shardings_fn, _ = build_fused_train_step(
        cfg, shape, mesh, tcfg)
    state, axes = init_fn(jax.random.PRNGKey(0))
    sh = shardings_fn(axes)
    jstep = jax.jit(step, in_shardings=(sh["state"], sh["batch"], sh["key"]),
                    out_shardings=(sh["state"], sh["metrics"]))
    kb = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(kb, (8, 64), 0, cfg.vocab),
        "labels": jax.random.randint(kb, (8, 64), 0, cfg.vocab),
    }
    losses = []
    for i in range(8):
        state, m = jstep(state, batch, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], losses
    # FSDP actually sharded the params over data axes
    emb = state["params"]["embed"]["table"]
    assert "data" in str(emb.sharding) or "data" in str(
        jax.tree.leaves(sh["state"]["params"])[0])
    print("losses", losses)
    print("OK")
    """)


def test_hierarchy_reduces_pod_crossing_traffic():
    """The W-HFL selling point: with the structural two-hop schedule the
    pod-crossing hop moves the CLUSTER estimate once, not every user's
    delta — visible as grouped all-reduces in the compiled HLO."""
    _run("""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.dist import OTADistConfig, whfl_aggregate, uniform_geom
    from repro.launch.mesh import refine_mesh
    from repro.launch.hlo import collective_stats
    from repro.sharding import shard_map

    mesh = jax.make_mesh((2, 4, 2), ("pod", "data", "model"))
    rmesh = refine_mesh(mesh, users_per_cluster=2)
    geom = uniform_geom(C=4, M=2)
    cfg = OTADistConfig(mode="equivalent", per_element_interference=False)

    def f(x, key):
        return whfl_aggregate({"w": x}, geom, key, 1.0, 20.0, cfg)["w"]

    g = jax.jit(shard_map(
        f, mesh=rmesh,
        in_specs=(P(("pod", "cluster", "user")), P()), out_specs=P(),
        check_vma=False))
    x = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
    k = jax.ShapeDtypeStruct((2,), jnp.uint32)
    txt = g.lower(x, k).compile().as_text()
    st = collective_stats(txt)
    groups = sorted(gs for (kind, gs) in st.by_group if kind == "all-reduce")
    # cluster hop: groups of 2 (users); global hop: groups of 4 (pod x cluster)
    assert any(gs == 2 for gs in groups), st.by_group
    assert any(gs == 4 for gs in groups), st.by_group
    print("groups", groups)
    print("OK")
    """, n_dev=16)
