"""The W-HFL round under `shard_map`: one (cluster, user) mesh, two
work splits, zero drift from the mesh shape.

Phase 1 — local training.  The per-user program is
`repro.core.whfl.make_local_train` (the same unit the single-device
engine vmaps); here every mesh shard `jax.lax.map`s it over its local
``(C_loc, M_loc)`` block of users.  `lax.map` runs the *identical*
per-slice program for every block size, so each user's delta is
bitwise the same no matter how many devices the users are spread over
(the established `batch="map"` property of the sweep engine, applied
to the user axis).

Phase 2 — the OTA hops.  The cluster hop with the ``fused`` backend is
the scaling path: every receiving IS hears every user, so the transmit
symbols are redistributed (all_to_all over symbols, all_gather over
clusters) and each shard runs the fused matched-filter combine for its
``C_loc`` rx stations x ``N_loc`` symbols, passing its tile origin as
the kernel's counter bases (`rx_base`/`n_base`).  The counter PRNG
keys on global (rx, u, k, n) indices only, so every shard draws
exactly the channels the full-range call would have drawn — the hop is
bitwise invariant to mesh shape, and there is *no* cross-device
reduction (the u/k folds happen entirely in-kernel, in a mesh-
independent block order).  All other backends (reference /
equivalent / ideal), the conventional baseline and the small IS -> PS
hop gather the (much smaller) inputs and compute replicated — the same
full-shape program on every device, which is trivially mesh-invariant.

Power accounting sums per-user energies locally, gathers the tiny
``[C, M]`` grid and folds it in a fixed order, again mesh-invariant.

Uneven meshes — any mesh runs any scenario.  When the mesh does not
divide (C, M), the workload is padded up to the mesh with *inactive*
users and clusters (`repro.exec.mesh.pad_plan_for`): padded users
train on zero dummy shards (``lax.map`` skips nothing — the per-slice
program stays identical, so real users' deltas are untouched) but
their transmissions never exist — every OTA hop, and the power
accounting, slices the gathered grid back to the real ``[:C, :M]``
block before computing, and the fused cluster hop drops inactive rows
so real users keep their *unpadded* global counter indices (their h/z
draws are exactly the single-engine draws; inactive rx stations get
zero-amplitude geometry rows and draw only at padded rx counters).
The result extends the mesh-invariance theorem to all meshes: a padded
sharded run is bitwise invariant to the mesh shape for every scenario,
and bitwise identical to the unpadded single-engine ``batch="map"``
run — final params, optimizer state, metrics and per-round power — for
the paper's scenarios, on both round drivers
(tests/test_uneven_mesh.py pins both).  Model state is bitwise
cross-engine everywhere; the one known exception is the scalar power
metrics on some odd fused-backend shapes, where XLA:CPU layout
assignment rounds the energy fold 1 ULP apart between the two
programs (bounded by the same tests).

Everything runs *fully manual* (both mesh axes): every collective is
explicit, so the program is the same on every backend.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import numpy as np

from repro.core import aggregation as agg
from repro.core.channel import (_cluster_geometry, _seed_words, cluster_ota,
                                conventional_ota, global_ota,
                                orthogonal_cluster_ota, resolve_backend)
from repro.core.topology import Topology
from repro.core.whfl import (WHFLConfig, make_local_train,
                             validate_participation)
from repro.exec.mesh import pad_plan_for
from repro.ft.guard import guard_estimate, validate_guard
from repro.kernels import fused_mac, interpret_mode
from repro.obs.telemetry import (cluster_telemetry, edge_telemetry_init,
                                 is_telemetry, is_telemetry_zero)
# the executor's symbol padding must agree with the kernel's rounding
from repro.kernels.fused_mac import (_round_up, canonical_block_u,
                                     fused_mac_partials, fused_noise,
                                     fused_partials_reduce)
from repro.optim import Optimizer, apply_updates
from repro.sharding import shard_map


COMBINES = ("gathered", "u_sharded")


def _build_round_parts(loss_fn: Callable, opt: Optimizer, topo: Topology,
                       cfg: WHFLConfig, spec: agg.FlatSpec, X, Y, mesh,
                       trace_counter: Optional[list] = None,
                       combine: str = "gathered"):
    """Construct the per-shard round body shared by both sharded entry
    points: `make_sharded_round_fn` (one shard_map per round) and
    `make_sharded_chunk_fn` (a lax.scan of the same body *inside* one
    shard_map per eval window).  Returns ``(_round, state_spec, X, Y)``
    where `_round(state, key, P_t, P_is_t, X_loc, Y_loc)` is valid only
    inside a shard_map over ``("cluster", "user")``.

    A mesh that does not divide (C, M) is handled by padding the
    workload with inactive users/clusters (`pad_plan_for`): the state's
    ``opt`` axes, the data shards and the per-shard layout all use the
    padded (Cp, Mp) grid, while every hop and the power accounting
    compute on the real ``[:C, :M]`` block only — see module docstring.
    Callers building states directly must size the opt axes to
    ``(plan.Cp, plan.Mp)`` (the sweep runners do this automatically).

    ``combine`` selects the fused cluster hop's distribution strategy:
    ``"gathered"`` (default) all-gathers the `[U, N_loc]` symbol block
    and runs the full-U kernel per shard; ``"u_sharded"`` keeps each
    cluster-axis shard's own user tile, runs the partial-combine
    kernel there and folds the per-tile accumulators in pinned global
    u-block order (`repro.kernels.fused_mac.fused_partials_reduce`),
    so no device ever materializes the full symbol block.  Both are
    bitwise equal to each other, to every mesh shape and to the single
    engine; for non-fused scenarios the flag is a Python-level no-op.
    """
    if combine not in COMBINES:
        raise ValueError(f"unknown combine {combine!r}; known: "
                         f"{', '.join(COMBINES)}")
    C, M = topo.C, topo.M
    plan = pad_plan_for(mesh, C, M)
    Cp, Mp = plan.Cp, plan.Mp
    mc, mu = mesh.devices.shape
    C_loc, M_loc = Cp // mc, Mp // mu
    two_n = spec.two_n
    N = two_n // 2
    Np = _round_up(N, mu)       # symbol axis padded to split over 'user'
    N_loc = Np // mu
    local_train = make_local_train(loss_fn, opt, cfg)
    interpret = interpret_mode()

    # Participation / robustness gates mirror the single engine's
    # Python-level branches (repro.core.whfl.make_round_fn): a full
    # schedule with the mean fold builds the identical pre-participation
    # program, and every participation op below composes with the pad
    # plan (a sampled-out user is a pad slot drawn per round: tx
    # multiplier 0, so its transmission never exists on any mesh).
    validate_participation(cfg)
    schedule = cfg.participation
    partial = not schedule.is_full
    robust = cfg.cluster_agg != "mean"
    # telemetry mirrors the single engine's Python-level gate: off
    # inserts nothing; on computes the identical fence-isolated
    # diagnostics from the *gathered* (real, unpadded) values, so the
    # block is replicated on every shard and mesh-invariant
    tele_on = cfg.telemetry
    # fault-tolerance gates (repro.ft), Python-level like the single
    # engine's: guard "off" / poison None insert nothing.  The guard
    # runs on the REPLICATED [Cp, 2N] estimate — padded rows are
    # exactly zero (finite), so the trip bit, the zeroing selections
    # and hence the guarded real rows are identical on every mesh and
    # to the single engine's [C, 2N] guard.
    validate_guard(cfg.guard)
    guard_on = cfg.guard != "off"
    poison = cfg.poison
    if poison is not None:
        if poison.c >= C or poison.m >= M:
            raise ValueError(
                f"poison targets user ({poison.c}, {poison.m}) outside "
                f"the ({C}, {M}) grid")
        _pmask = np.zeros((C, M), bool)
        _pmask[poison.c, poison.m] = True
        _pmask_p = jnp.asarray(plan.pad_users(_pmask))     # [Cp, Mp]

    def maybe_poison_loc(flat_loc, step, ci, ui):
        """Poison the fold input of this shard's block iff it owns the
        targeted user — the same per-coordinate `flat + where(...)`
        the single engine applies, restricted to the local tile, so
        the poisoned symbols are bitwise cross-engine.  Python-level
        no-op when poison is None."""
        if poison is None:
            return flat_loc
        mask_loc = jax.lax.dynamic_slice(
            _pmask_p, (ci * C_loc, ui * M_loc), (C_loc, M_loc))
        hit = jnp.logical_and(step == poison.t, mask_loc)
        return flat_loc + jnp.where(hit, poison.value, 0.0)[..., None]

    tx_base = jnp.asarray(schedule.tx_base(C, M)) if partial else None
    rx_w = (np.ones((C, M), np.float32) if cfg.ota.mode == "ideal"
            else np.asarray(topo.beta_own, np.float32))
    rx_w_conv = (np.ones((C, M), np.float32) if cfg.ota.mode == "ideal"
                 else np.asarray(topo.beta_mu_ps, np.float32))

    backend = ("" if cfg.ota.mode == "ideal" else resolve_backend(cfg.ota))
    fused_cluster_hop = (cfg.mode != "conventional" and backend == "fused")
    if fused_cluster_hop:
        amp, own, bb = _cluster_geometry(topo, cfg.ota)     # [C, U], .., [C]
        # inactive rx stations: amp = w = 0 rows (their matched filter,
        # and hence their combined output, is exactly zero); bb pads
        # with 1 so the rescale stays finite.  The user axis keeps the
        # real U — inactive users are dropped before the kernel call
        # (user_perm below), so real users' counter indices, and with
        # them every h/z draw, are exactly the unpadded full call's.
        amp = plan.pad_rx(amp)                              # [Cp, U]
        own = plan.pad_rx(own)
        bb = plan.pad_rx(bb, fill=1.0)                      # [Cp]
        user_perm = jnp.asarray(plan.user_perm())           # [U] static
        # the canonical u-blocking shared with the single engine: it
        # divides M, so u-blocks never straddle a cluster — and with it
        # a u-shard — boundary, and the partial fold can replay the
        # full call's accumulation order
        bu_c = canonical_block_u(M)
        if combine == "u_sharded":
            # virtual user axis [Cp * M]: real users keep their global
            # c * M + m index (padded clusters append at the end), so
            # shard cj owns the contiguous tile [cj*C_loc*M, ...).
            # Padded clusters' virtual users get zero amp/w columns;
            # their blocks are strictly trailing and the fold drops
            # them (G_real below) — they never touch a real bit.
            amp_v = jnp.pad(amp, ((0, 0), (0, (Cp - C) * M)))
            own_v = jnp.pad(own, ((0, 0), (0, (Cp - C) * M)))
            bk_c = min(8, topo.K)
            Kp_c = _round_up(topo.K, bk_c)
            G_real = C * M // bu_c

    X = plan.pad_users(jnp.asarray(X))   # inactive users: zero shards
    Y = plan.pad_users(jnp.asarray(Y))

    # -- helpers (valid inside shard_map over ('cluster', 'user')) ----------

    def _gather_cm(x_loc):
        """[C_loc, M_loc, ...] shard -> full [Cp, Mp, ...] on every
        device, sliced back to the real [C, M, ...] block (inactive
        users never reach a hop or the power fold)."""
        x = jax.lax.all_gather(x_loc, "user", axis=1, tiled=True)
        x = jax.lax.all_gather(x, "cluster", axis=0, tiled=True)
        return plan.unpad_users(x)

    def _slice_c(tree, ci):
        """Replicated [Cp, ...] pytree -> this shard's [C_loc, ...] rows."""
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, ci * C_loc, C_loc, 0),
            tree)

    @jax.named_scope("whfl.train")
    def users_train(theta_IS, opt_loc, key, step, X_loc, Y_loc, ci, ui,
                    mult_p=None):
        """Local training of this shard's users.

        theta_IS: replicated [Cp]-stacked cluster models; opt/X/Y: the
        shard's [C_loc, M_loc, ...] block.  Returns (flat deltas
        [C_loc, M_loc, 2N], opt state, per-user energies [C_loc, M_loc]).
        The per-user key grid is derived over the REAL (C, M) grid
        exactly as in the single-device engine — inactive users get a
        dummy zero key — and sliced to the local block, so user (c, m)
        trains from the same key on every mesh (and every real delta is
        bitwise the single-engine delta; inactive deltas are computed
        but never transmitted).

        `mult_p` (padded [Cp, Mp], participation runs only): the round's
        COTAF transmit multipliers.  Each user's flat delta is precoded
        *before* its energy is computed inside the per-user map, the
        same elementwise multiply the single engine batches
        (`agg.cotaf_precode`), so precoded symbols AND energies stay
        bitwise cross-engine; padded slots carry multiplier 0 (a
        sampled-out user is exactly a pad slot).
        """
        keys = jax.random.split(key, C * M).reshape(C, M, 2)
        keys = plan.pad_users(keys)                     # [Cp, Mp, 2]
        keys_loc = jax.lax.dynamic_slice(
            keys, (ci * C_loc, ui * M_loc, 0), (C_loc, M_loc, 2))
        theta_loc = _slice_c(theta_IS, ci)
        if partial:
            mult_loc = jax.lax.dynamic_slice(
                mult_p, (ci * C_loc, ui * M_loc), (C_loc, M_loc))

        def one_cluster(args):
            if partial:
                th_c, opt_c, x_c, y_c, k_c, m_c = args
            else:
                th_c, opt_c, x_c, y_c, k_c = args

            def one_user(a):
                if partial:
                    st, x, y, k, m = a
                else:
                    st, x, y, k = a
                delta, st = local_train(th_c, st, x, y, k, step)
                flat = agg.flatten(spec, delta)
                if partial:
                    flat = flat * m
                with jax.named_scope("whfl.update"):   # power accounting
                    energy = agg.user_energy(flat)
                return flat, st, energy

            xs = ((opt_c, x_c, y_c, k_c, m_c) if partial
                  else (opt_c, x_c, y_c, k_c))
            return jax.lax.map(one_user, xs)

        xs = ((theta_loc, opt_loc, X_loc, Y_loc, keys_loc, mult_loc)
              if partial else (theta_loc, opt_loc, X_loc, Y_loc, keys_loc))
        flat, opt_loc, pw = jax.lax.map(one_cluster, xs)
        return flat, opt_loc, pw

    def edge_power(pw_loc, P_t):
        """Mesh-invariant `agg.symbol_power`: per-user energies are
        gathered to the tiny real [C, M] grid (inactive users sliced
        off) and folded through the same fenced subgraph the single
        engine uses (`agg.symbol_power_from_energy`), so the scalar is
        bitwise identical across meshes (and across engines for the
        paper scenarios — see module docstring)."""
        pw = _gather_cm(pw_loc)
        return agg.symbol_power_from_energy(pw, P_t, N)

    def fused_cluster_estimate(key, flat_loc, P_t, ci, ui):
        """Sharded fused cluster hop: rx stations over 'cluster',
        symbols over 'user', channels drawn in-kernel at the shard's
        global tile origin.  Returns the replicated [Cp, 2N] estimate
        whose real rows are identical to `FusedBackend.cluster` on one
        device (inactive rows are exactly zero)."""
        # redistribute (users -> symbols): [C_loc, M_loc, N] local users
        # with all symbols  ->  [U, N_loc] all users, local symbols.
        # The padded-grid rows come back in (Cp, Mp) order; gathering
        # `user_perm` drops inactive users AND restores the unpadded
        # c*M + m user order, so the kernel sees the exact [U, N] tile
        # (and counter indices) of the single-engine call.
        def redistribute(t):
            t = jnp.pad(t, ((0, 0), (0, 0), (0, Np - N)))
            t = jax.lax.all_to_all(t, "user", split_axis=2, concat_axis=1,
                                   tiled=True)           # [C_loc, Mp, N_loc]
            t = jax.lax.all_gather(t, "cluster", axis=0, tiled=True)
            t = t.reshape(Cp * Mp, N_loc)
            return t if plan.is_identity else jnp.take(t, user_perm, axis=0)

        t_re = P_t * redistribute(flat_loc[..., :N])
        t_im = P_t * redistribute(flat_loc[..., N:])
        amp_loc = jax.lax.dynamic_slice_in_dim(amp, ci * C_loc, C_loc, 0)
        own_loc = jax.lax.dynamic_slice_in_dim(own, ci * C_loc, C_loc, 0)
        bb_loc = jax.lax.dynamic_slice_in_dim(bb, ci * C_loc, C_loc, 0)
        # block sizes depend only on the GLOBAL workload shape (never on
        # the mesh), so the per-element accumulation order — and with it
        # the bitwise mesh-invariance — is preserved: the u-blocking is
        # the canonical one every fused cluster-hop path shares
        y_re, y_im = fused_mac(
            _seed_words(key), t_re, t_im, amp_loc, own_loc, K=topo.K,
            sigma_h2=topo.sigma_h2, sigma_z2=topo.sigma_z2,
            rx_base=ci * C_loc, n_base=ui * N_loc, block_u=bu_c,
            interpret=interpret)
        scale = P_t * topo.sigma_h2 * bb_loc[:, None]

        def collect(y):                       # [C_loc, N_loc] -> [Cp, N]
            y = jax.lax.all_gather(y, "user", axis=1, tiled=True)[:, :N]
            return jax.lax.all_gather(y, "cluster", axis=0, tiled=True)

        est_re = collect(y_re / topo.K / scale)
        est_im = collect(y_im / topo.K / scale)
        return jnp.concatenate([est_re, est_im], axis=-1)   # [Cp, 2N]

    def fused_cluster_estimate_u_sharded(key, flat_loc, P_t, ci, ui):
        """U-sharded fused cluster hop: each cluster-axis shard runs
        the partial-combine kernel over only its own user tile (all Cp
        rx rows, local symbols), then every shard folds the gathered
        per-tile accumulators in pinned ascending u-block order — a
        fixed sequential chain (`fori_loop`), never a `psum` — with the
        noise drawn exactly once per (rx, k, n) as a separate term on
        the kernel's own counter stream (`fused_noise`).  The
        `[U, N_loc]` symbol block never exists on any device: per-shard
        symbol memory is O(U / mc * N_loc) + the K-resolved partials.
        Returns the replicated [Cp, 2N] estimate, bitwise
        `fused_cluster_estimate` (pinned by tests/test_exec_sharded.py).
        """
        U_loc = C_loc * M          # virtual users per cluster-axis shard

        def to_tile(t):
            # [C_loc, M_loc, N] local users -> this shard's user tile
            # with local symbols.  Same all_to_all as the gathered
            # path, but no cluster-axis gather: the shard keeps only
            # its own C_loc clusters' users.  Slicing [:, :M] drops the
            # padded per-cluster slots (pad_users appends them), so
            # rows are the real users in c * M + m order.
            t = jnp.pad(t, ((0, 0), (0, 0), (0, Np - N)))
            t = jax.lax.all_to_all(t, "user", split_axis=2, concat_axis=1,
                                   tiled=True)         # [C_loc, Mp, N_loc]
            return t[:, :M].reshape(U_loc, N_loc)

        t_re = P_t * to_tile(flat_loc[..., :N])
        t_im = P_t * to_tile(flat_loc[..., N:])
        u0 = ci * U_loc            # this tile's global u-block origin
        amp_t = jax.lax.dynamic_slice_in_dim(amp_v, u0, U_loc, 1)
        own_t = jax.lax.dynamic_slice_in_dim(own_v, u0, U_loc, 1)
        words = _seed_words(key)
        pr_re, pr_im, pm_re, pm_im = fused_mac_partials(
            words, t_re, t_im, amp_t, own_t, K=topo.K,
            sigma_h2=topo.sigma_h2, rx_base=0, u_base=u0,
            n_base=ui * N_loc, block_u=bu_c,
            interpret=interpret)            # 4 x [Cp, G_loc, Kp, N_loc]

        def order(p):
            # gather every shard's blocks and lay them out in global
            # u-block order (shard d owns blocks [d*G_loc, (d+1)*G_loc)),
            # then drop the strictly-trailing inactive-cluster blocks
            p = jax.lax.all_gather(p, "cluster", axis=0)
            G_loc = p.shape[2]
            p = jnp.moveaxis(p, 0, 1).reshape(Cp, mc * G_loc, Kp_c, N_loc)
            return p[:, :G_real]

        z_re, z_im = fused_noise(words, Cp, Kp_c, N_loc, topo.sigma_z2,
                                 rx_base=0, n_base=ui * N_loc)
        y_re, y_im = fused_partials_reduce(
            order(pr_re), order(pr_im), order(pm_re), order(pm_im),
            z_re, z_im, K=topo.K)
        # y is replicated over 'cluster' (every shard folded the same
        # gathered blocks); the same per-element rescale as the
        # gathered path, then one symbol-axis gather
        scale = P_t * topo.sigma_h2 * bb[:, None]

        def collect(y):                       # [Cp, N_loc] -> [Cp, N]
            return jax.lax.all_gather(y, "user", axis=1, tiled=True)[:, :N]

        est_re = collect(y_re / topo.K / scale)
        est_im = collect(y_im / topo.K / scale)
        return jnp.concatenate([est_re, est_im], axis=-1)   # [Cp, 2N]

    def cluster_estimate(key, flat_loc, P_t, ci, ui, claimed=None):
        """Replicated [Cp, 2N] cluster estimate; real rows == the
        single-engine cluster fold, inactive rows zero (padded with a
        1.0 rescale, so they stay exactly zero under participation).

        Mirrors `repro.core.whfl.make_round_fn`'s `cluster_fold`: OTA
        superposition mean (+ COTAF attendance rescale under partial
        participation) or a robust masked fold over orthogonalized
        per-user receptions (small backends only, computed replicated
        on the gathered real block — the literal single-engine
        program, hence bitwise cross-engine/mesh)."""
        if fused_cluster_hop:
            est = (fused_cluster_estimate_u_sharded(key, flat_loc, P_t,
                                                    ci, ui)
                   if combine == "u_sharded" else
                   fused_cluster_estimate(key, flat_loc, P_t, ci, ui))
            if partial:
                resc = agg.attendance_rescale(rx_w, claimed)    # [C]
                est = est * plan.pad_rx(resc, fill=1.0)[:, None]
            return est
        flat = _gather_cm(flat_loc)
        if robust:
            mask = (claimed if partial
                    else jnp.ones((C, M), jnp.float32))
            per_user = orthogonal_cluster_ota(key, flat, topo, P_t,
                                              cfg.ota)
            if cfg.cluster_agg == "median":
                return plan.pad_rx(agg.masked_median(per_user, mask))
            return plan.pad_rx(
                agg.masked_trimmed_mean(per_user, mask, cfg.agg_trim))
        # small/closed-form backends: gather the real block and compute
        # replicated — the literal single-engine hop on identical input
        # (inactive clusters receive a zero-padded estimate row)
        est = cluster_ota(key, flat, topo, P_t, cfg.ota)
        if partial:
            est = est * agg.attendance_rescale(rx_w, claimed)[:, None]
        return plan.pad_rx(est)

    # -- the round body ------------------------------------------------------

    def _round(state, key, P_t, P_is_t, X_loc, Y_loc):
        if trace_counter is not None:
            trace_counter[0] += 1  # python side effect: runs at trace time
        ci = jax.lax.axis_index("cluster")
        ui = jax.lax.axis_index("user")
        theta = state["theta"]
        step = state["t"]
        if partial:
            # replicated on every shard: the mask is a pure function of
            # (schedule, step) through the counter PRNG, so all shards
            # (and the single engine) draw the identical [C, M] grid
            claimed = schedule.present(step, C, M)
            mult_p = plan.pad_users(claimed * tx_base)      # [Cp, Mp]
        else:
            claimed = mult_p = None
        theta_IS = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (Cp,) + x.shape), theta)

        if cfg.mode == "conventional":
            k1, k2 = jax.random.split(key)
            flat_loc, opt_state, pw = users_train(
                theta_IS, state["opt"], k1, step, X_loc, Y_loc, ci, ui,
                mult_p)
            with jax.named_scope("whfl.ps_hop"):
                flat = _gather_cm(flat_loc)
                est = conventional_ota(
                    k2, _gather_cm(maybe_poison_loc(flat_loc, step, ci, ui))
                    if poison is not None else flat, topo, P_t, cfg.ota)
                if partial:
                    est = est * agg.attendance_rescale(
                        rx_w_conv.reshape(-1), claimed.reshape(-1))
            with jax.named_scope("whfl.update"):
                if guard_on:
                    est, g_trip = guard_estimate(est, cfg.guard)
                theta = apply_updates(theta, agg.unflatten(spec, est))
                out = {**state, "theta": theta, "opt": opt_state,
                       "t": step + 1,
                       "power_edge": (state["power_edge"]
                                      + edge_power(pw, P_t)),
                       "n_edge_tx": state["n_edge_tx"] + 1.0,
                       "power_is": state["power_is"],
                       "n_is_tx": state["n_is_tx"]}
                if guard_on:
                    out["guard_trips"] = state["guard_trips"] + g_trip
            if tele_on:
                out["telemetry"] = {
                    **cluster_telemetry(flat, est, claimed, topo, P_t,
                                        mode="conventional"),
                    **is_telemetry_zero()}
            return out

        # --- W-HFL ---
        def cluster_iter(carry, k):
            th_IS, opt_state, p_acc = carry[:3]
            g_acc = carry[3] if guard_on else None
            k1, k2 = jax.random.split(k)
            flat_loc, opt_state, pw = users_train(
                th_IS, opt_state, k1, step, X_loc, Y_loc, ci, ui, mult_p)
            with jax.named_scope("whfl.cluster_hop"):
                est = cluster_estimate(
                    k2, maybe_poison_loc(flat_loc, step, ci, ui), P_t, ci,
                    ui, claimed)                             # [Cp, 2N]
            with jax.named_scope("whfl.update"):
                if guard_on:
                    est, g_trip = guard_estimate(est, cfg.guard)
                    g_acc = g_acc + g_trip
                th_IS = jax.vmap(
                    lambda th, e: apply_updates(th, agg.unflatten(spec, e))
                )(th_IS, est)
                out = (th_IS, opt_state, p_acc + edge_power(pw, P_t))
                if guard_on:
                    out += (g_acc,)
            if tele_on:
                # the last cluster iteration's block survives
                # gathered real [C, M, 2N] deltas + real estimate rows:
                # the literal single-engine telemetry inputs, computed
                # replicated (opt-in cost; the off-path has no gather)
                est_r = est if Cp == C else est[:C]
                out += (cluster_telemetry(_gather_cm(flat_loc), est_r,
                                          claimed, topo, P_t),)
            return out, None

        keys = jax.random.split(key, cfg.I + 1)
        carry0 = (theta_IS, state["opt"], jnp.zeros(()))
        if guard_on:
            carry0 += (jnp.zeros((), jnp.int32),)
        if tele_on:
            carry0 += (edge_telemetry_init(C),)
        carry, _ = jax.lax.scan(cluster_iter, carry0, keys[: cfg.I])
        theta_IS, opt_state, p_edge = carry[:3]
        g_edge = carry[3] if guard_on else None
        tele_blk = carry[3 + int(guard_on)] if tele_on else None

        # only the real clusters transmit to the PS
        with jax.named_scope("whfl.ps_hop"):
            theta_IS_act = (theta_IS if Cp == C else
                            jax.tree.map(lambda x: x[:C], theta_IS))
            is_deltas = jax.vmap(
                lambda th: agg.flatten(
                    spec, jax.tree.map(lambda a, b: a - b, th, theta)))(
                        theta_IS_act)
            est = global_ota(keys[-1], is_deltas, topo, P_is_t, cfg.ota)
        with jax.named_scope("whfl.update"):
            if guard_on:
                est, g_is = guard_estimate(est, cfg.guard)
            theta = apply_updates(theta, agg.unflatten(spec, est))
            p_is = agg.symbol_power(is_deltas, P_is_t)
            out = {**state, "theta": theta, "opt": opt_state,
                   "t": step + 1,
                   "power_edge": state["power_edge"] + p_edge,
                   "n_edge_tx": state["n_edge_tx"] + float(cfg.I),
                   "power_is": state["power_is"] + p_is,
                   "n_is_tx": state["n_is_tx"] + 1.0}
            if guard_on:
                out["guard_trips"] = state["guard_trips"] + g_edge + g_is
        if tele_on:
            out["telemetry"] = {**tele_blk,
                                **is_telemetry(is_deltas, topo, P_is_t)}
        return out

    state_spec = {
        "theta": P(), "opt": P("cluster", "user"), "t": P(),
        "power_edge": P(), "power_is": P(), "n_edge_tx": P(),
        "n_is_tx": P(),
    }
    if tele_on:
        # the whole diagnostics block is computed from gathered values,
        # hence replicated (the tree-prefix P() covers every leaf)
        state_spec["telemetry"] = P()
    if guard_on:
        # computed from the replicated estimates, hence replicated
        state_spec["guard_trips"] = P()
    return _round, state_spec, X, Y


def make_sharded_round_fn(loss_fn: Callable, opt: Optimizer, topo: Topology,
                          cfg: WHFLConfig, spec: agg.FlatSpec, X, Y, mesh,
                          trace_counter: Optional[list] = None,
                          combine: str = "gathered") -> Callable:
    """Build ``round_fn(state, key, P_t, P_is_t) -> state`` running one
    W-HFL round sharded over `mesh` (axes ``("cluster", "user")``).

    Same contract as `repro.core.whfl.make_round_fn` — pure, jit-able,
    seed-batchable — plus the mesh-invariance guarantee: for a fixed
    scenario and seed, the returned state is bitwise identical for
    EVERY mesh shape, including ``1x1`` and meshes that do not divide
    (C, M) — those run with inactive-user padding
    (`repro.exec.mesh.pad_plan_for`), and the state's ``opt`` axes must
    then be sized ``(plan.Cp, plan.Mp)`` (e.g.
    ``init_round_state(params, opt, plan.Cp, plan.Mp)``; the sweep
    runners do this automatically).  Pinned by
    `tests/test_exec_sharded.py` and `tests/test_uneven_mesh.py`.
    """
    _round, state_spec, X, Y = _build_round_parts(
        loss_fn, opt, topo, cfg, spec, X, Y, mesh,
        trace_counter=trace_counter, combine=combine)
    sharded = shard_map(
        _round, mesh=mesh,
        in_specs=(state_spec, P(), P(), P(),
                  P("cluster", "user"), P("cluster", "user")),
        out_specs=state_spec, check_vma=False)

    def round_fn(state, key, P_t, P_is_t):
        return sharded(state, key, jnp.float32(P_t), jnp.float32(P_is_t),
                       X, Y)

    return round_fn


def make_sharded_chunk_fn(loss_fn: Callable, opt: Optimizer, topo: Topology,
                          cfg: WHFLConfig, spec: agg.FlatSpec, X, Y, mesh,
                          eval_fn: Optional[Callable] = None,
                          trace_counter: Optional[list] = None,
                          combine: str = "gathered") -> Callable:
    """Build ``chunk_fn(state, key, P_win, P_is_win) -> (state, key,
    metrics)`` running ``len(P_win)`` sharded W-HFL rounds in a single
    `lax.scan` *inside* one shard_map — the sharded-engine counterpart
    of `repro.core.whfl.make_chunk_fn`, so the host stops paying a
    shard_map re-entry + dispatch barrier per round.

    The scan body is exactly the `_round` body the per-round entry
    point runs (same key chain as the stepwise driver: ``key, sub =
    split(key)`` per round — threefry is integer-exact and replicated
    identically on every shard), so chunked sharded sweeps are bitwise
    equal to stepwise sharded sweeps AND retain the engine's bitwise
    mesh-invariance.  `eval_fn(state)` (optional) is folded into the
    same jitted program on the replicated post-window state.
    """
    _round, state_spec, X, Y = _build_round_parts(
        loss_fn, opt, topo, cfg, spec, X, Y, mesh,
        trace_counter=trace_counter, combine=combine)

    def _chunk(state, key, P_win, P_is_win, X_loc, Y_loc):
        def body(carry, Ps):
            st, k = carry
            ks = jax.random.split(k)
            st = _round(st, ks[1], Ps[0], Ps[1], X_loc, Y_loc)
            return (st, ks[0]), None

        (state, key), _ = jax.lax.scan(body, (state, key),
                                       (P_win, P_is_win))
        return state, key

    sharded = shard_map(
        _chunk, mesh=mesh,
        in_specs=(state_spec, P(), P(), P(),
                  P("cluster", "user"), P("cluster", "user")),
        out_specs=(state_spec, P()), check_vma=False)

    def chunk_fn(state, key, P_win, P_is_win):
        state, key = sharded(state, key,
                             jnp.asarray(P_win, jnp.float32),
                             jnp.asarray(P_is_win, jnp.float32), X, Y)
        metrics = None
        if eval_fn is not None:
            with jax.named_scope("whfl.eval"):
                metrics = eval_fn(state)
        return state, key, metrics

    return chunk_fn
