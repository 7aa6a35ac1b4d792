"""Named phases of the round on the compiled program, and the sweep
drivers' host spans on the profiler's clock.

Every scope of `repro.core.whfl.SCOPES` must reach the ``op_name``
metadata of the compiled chunk program, in both engines, whatever the
channel and the seed batching; a ``--profile`` run of the chunked
driver with checkpoints must hold the ``sweep.dispatch``,
``sweep.fetch`` and ``sweep.checkpoint`` host spans."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_forced_devices

from repro.core.whfl import SCOPES

HERE = os.path.dirname(os.path.abspath(__file__))
OP_NAME = re.compile(r'op_name="([^"]*)"')


def scopes_in(hlo_text):
    """The SCOPES named in any op_name of an HLO module's text."""
    ops = OP_NAME.findall(hlo_text)
    return {s for s in SCOPES
            if any(re.search(re.escape(s) + r"(?![\w.])", o) for o in ops)}


def compiled_chunk_text(runner, sc):
    """HLO text of `runner`'s compiled chunk program (eval folded in)
    for scenario `sc`, built from the runner's own engine hooks."""
    from repro.core import aggregation as agg
    from repro.nn.core import split_params
    from repro.optim import adam

    init_fn, apply_fn, loss_fn = sc.task_fns()
    X, Y, xte, _ = sc.make_data()
    topo, cfg, opt = sc.make_topology(), sc.whfl_config(), adam(sc.lr)
    params = [split_params(init_fn(jax.random.PRNGKey(s)))[0]
              for s in runner.seeds]
    spec = agg.make_flat_spec(params[0])
    states = runner._init_states(params, opt, topo, cfg)
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in runner.seeds])
    xte = jnp.asarray(xte)

    def eval_state(st):
        return jnp.mean(apply_fn(st["theta"], xte))

    chunk = runner._build_chunk(sc, loss_fn, opt, topo, cfg, spec, X, Y,
                                [0], eval_state)
    fn, args = ((chunk, ()) if hasattr(chunk, "lower")
                else (chunk.func, chunk.args))
    P = np.ones((1,), np.float32)
    return fn.lower(*args, state, keys, P, P).compile().as_text()


def fig2_quick(ota_mode, backend):
    from repro.sim import get_scenario
    return get_scenario("fig2_iid").quick().replace(
        ota_mode=ota_mode, ota_backend=backend, total_IT=1, eval_every=1)


@pytest.mark.parametrize("batch", ["vmap", "map"])
@pytest.mark.parametrize("ota_mode,backend", [("equivalent", ""),
                                              ("faithful", "fused")])
def test_single_engine_chunk_names_every_phase(ota_mode, backend, batch):
    from repro.sim.sweep import SweepRunner
    sc = fig2_quick(ota_mode, backend)
    runner = SweepRunner([sc], seeds=2, batch=batch, driver="chunked")
    assert scopes_in(compiled_chunk_text(runner, sc)) == set(SCOPES)


def test_sharded_chunk_names_every_phase():
    out = run_forced_devices(f"""
    import sys
    sys.path.insert(0, {HERE!r})
    from repro.exec import ShardedSweepRunner
    from test_scopes import compiled_chunk_text, fig2_quick, scopes_in

    sc = fig2_quick("faithful", "fused")
    runner = ShardedSweepRunner([sc], seeds=1, mesh="2x4",
                                driver="chunked")
    print(sorted(scopes_in(compiled_chunk_text(runner, sc))))
    """)
    assert out.strip().splitlines()[-1] == str(sorted(SCOPES))


def test_sweep_profile_holds_the_drivers_host_spans(tmp_path):
    from jax.profiler import ProfileData

    from repro.sim.sweep import main
    prof = tmp_path / "profile"
    main(["--quick", "--driver", "chunked", "--seeds", "1",
          "--checkpoint", str(tmp_path / "ckpt"), "--profile", str(prof)])
    files = glob.glob(str(prof / "**" / "*.xplane.pb"), recursive=True)
    assert files
    spans = {e.name for f in files
             for plane in ProfileData.from_file(f).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"sweep.dispatch", "sweep.fetch", "sweep.checkpoint"} <= spans
