"""`ShardedSweepRunner`: the sweep engine on a device mesh.

Drop-in `repro.sim.SweepRunner` subclass — same scenarios, same seed
batching, same JSON schema — that swaps the single-device round for
`repro.exec.round.make_sharded_round_fn` on a ``("cluster", "user")``
mesh.  Seeds run through ``jax.lax.map`` (the bitwise-reproducible
batch mode), so a sweep slice equals the same seed swept alone *and*
the whole trajectory is bitwise invariant to the mesh shape: the
``1x1`` mesh is the reference run and ``2x4`` reproduces it exactly
(`tests/test_exec_sharded.py`).

    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.sim.sweep --scenarios scale_u256 --seeds 2 \
            --exec sharded --mesh 2x4
"""
from __future__ import annotations

from typing import Dict, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core.topology import PadPlan, pad_plan
from repro.core.whfl import init_round_state
from repro.exec.mesh import make_device_mesh, parse_mesh
from repro.exec.round import (COMBINES, make_sharded_chunk_fn,
                              make_sharded_round_fn)
from repro.kernels.fused_mac import _round_up, canonical_block_u
from repro.sim.scenario import Scenario
from repro.sim.sweep import SweepRunner


class ShardedSweepRunner(SweepRunner):
    """Run scenarios sharded over a ``(cluster, user)`` device mesh.

    mesh: ``"CxU"`` string or ``(C_shards, U_shards)`` tuple.  A
    scenario need NOT divide the mesh: when it doesn't, the workload is
    padded with inactive users (amp = w = 0; `pad_plan_for`) — the
    ``opt`` state axes are sized to the padded (Cp, Mp) grid here and
    stripped again before ``final_state`` is stored, so results (and
    final states) stay bitwise identical to the unpadded single-engine
    run.  The symbol axis of the fused OTA hop is likewise padded to
    split evenly.
    The seed axis always uses the ``map`` batch mode — the sharded
    engine's contract is bitwise reproducibility, which vmap's
    batch-size-dependent lowering would break.  Both round drivers are
    supported: ``driver="chunked"`` scans the round body *inside* the
    shard_map (`make_sharded_chunk_fn`), removing the per-round host
    barrier while staying bitwise equal to stepwise.
    """

    def __init__(self, scenarios: Sequence[Union[str, Scenario]],
                 seeds=1, quick: bool = False, keep_state: bool = False,
                 mesh: Union[str, tuple] = "1x1",
                 driver: str = "stepwise", warmup: bool = False,
                 telemetry: bool = False, trace=None,
                 checkpoint=None, ckpt_every: int = 1,
                 resume: bool = False, guard: str = "off", faults=None,
                 combine: str = "gathered"):
        super().__init__(scenarios, seeds=seeds, quick=quick,
                         keep_state=keep_state, batch="map",
                         driver=driver, warmup=warmup,
                         telemetry=telemetry, trace=trace,
                         checkpoint=checkpoint, ckpt_every=ckpt_every,
                         resume=resume, guard=guard, faults=faults)
        if combine not in COMBINES:
            raise ValueError(f"unknown combine {combine!r}; known: "
                             f"{', '.join(COMBINES)}")
        self.combine = combine
        self.mesh_shape = parse_mesh(mesh)
        self.mesh = make_device_mesh(self.mesh_shape)

    def _pad_plan(self, topo) -> PadPlan:
        """The inactive-user embedding of this runner's mesh for one
        scenario's (C, M) workload (identity when the mesh divides)."""
        return pad_plan(topo.C, topo.M, self.mesh_shape)

    def _init_states(self, params, opt, topo, cfg):
        plan = self._pad_plan(topo)
        # telemetry is computed from the gathered *real* (C, M) values,
        # so its cluster axis is topo.C even on a padded mesh
        tele_C = topo.C if cfg.telemetry else None
        return [init_round_state(p, opt, plan.Cp, plan.Mp,
                                 telemetry_C=tele_C,
                                 guard=cfg.guard != "off")
                for p in params]

    def _finalize_state(self, state, topo):
        """Strip the padded opt rows/cols (leading axis is the seed
        batch) so final states compare tree-equal across engines and
        meshes — this canonical (C, M) view is also what checkpoints
        store, making a checkpoint mesh-portable."""
        plan = self._pad_plan(topo)
        if plan.is_identity:
            return state
        state = dict(state)
        state["opt"] = jax.tree.map(lambda x: x[:, : topo.C, : topo.M],
                                    state["opt"])
        return state

    def _restore_state(self, state, topo):
        """Inverse of `_finalize_state` for resume: re-pad the opt axes
        of a canonical (C, M) checkpoint to this mesh's (Cp, Mp) grid.
        Zero-filled pad rows are exact — a padded user's opt state is
        carried but never transmitted, and `_finalize_state` strips it
        again, so the resumed trajectory is bitwise the checkpointing
        mesh's (cross-mesh resume; CI gates it at --max-ulp 0)."""
        plan = self._pad_plan(topo)
        if plan.is_identity:
            return state
        state = dict(state)

        def pad(x):   # [S, C, M, ...] -> [S, Cp, Mp, ...]
            x = jnp.asarray(x)
            width = [(0, 0), (0, plan.Cp - topo.C),
                     (0, plan.Mp - topo.M)] + [(0, 0)] * (x.ndim - 3)
            return jnp.pad(x, width)

        state["opt"] = jax.tree.map(pad, state["opt"])
        return state

    def _build_round(self, sc, loss_fn, opt, topo, cfg, spec, X, Y, counter):
        round_fn = make_sharded_round_fn(loss_fn, opt, topo, cfg, spec,
                                         X, Y, self.mesh,
                                         trace_counter=counter,
                                         combine=self.combine)
        return self._batch_round(round_fn)

    def _build_chunk(self, sc, loss_fn, opt, topo, cfg, spec, X, Y, counter,
                     eval_fn):
        """Seed-batched sharded chunk: the round scan runs *inside* the
        shard_map (`make_sharded_chunk_fn`); the per-seed chunk (incl.
        the per-seed eval on the replicated post-window state) is then
        lax.map'ed over seeds exactly like the stepwise sharded round,
        and the carried (state, keys) buffers are donated."""
        chunk_fn = make_sharded_chunk_fn(loss_fn, opt, topo, cfg, spec,
                                         X, Y, self.mesh, eval_fn=eval_fn,
                                         trace_counter=counter,
                                         combine=self.combine)

        def batched(st, ks, P_win, P_is_win):
            return jax.lax.map(
                lambda a: chunk_fn(a[0], a[1], P_win, P_is_win), (st, ks))

        return jax.jit(batched, donate_argnums=(0, 1))

    def _exec_info(self, topo=None, two_n=None, n_user=None) -> Dict:
        mc, mu = self.mesh_shape
        info = super()._exec_info(n_user=n_user)
        info.update(name="sharded", mesh=f"{mc}x{mu}",
                    device_count=mc * mu, padded=None,
                    combine=self.combine)
        if topo is not None:
            plan = self._pad_plan(topo)
            if not plan.is_identity:
                info["padded"] = f"{plan.Cp}x{plan.Mp}"
            if two_n is not None:
                info["peak_symbol_bytes"] = self._peak_symbol_bytes(
                    topo, plan, two_n)
        return info

    def _peak_symbol_bytes(self, topo, plan, two_n) -> int:
        """Per-device peak bytes of fused cluster-hop *symbol-domain*
        buffers (f32 tx symbols + the K-resolved partial accumulators),
        the memory the ``combine`` strategy actually moves: gathered
        materializes the full [Cp*Mp, N_loc] block on every device;
        u_sharded keeps only the shard's own user tile plus the
        (much smaller for large U) gathered partials."""
        mc, mu = self.mesh_shape
        N_loc = _round_up(two_n // 2, mu) // mu
        if self.combine == "gathered":
            return 8 * plan.Cp * plan.Mp * N_loc
        bu = canonical_block_u(topo.M)
        bk = min(8, topo.K)
        Kp = _round_up(topo.K, bk)
        G_tot = plan.Cp * topo.M // bu
        return (8 * (plan.Cp // mc) * plan.Mp * N_loc
                + 16 * plan.Cp * G_tot * Kp * N_loc)
