"""The W-HFL round under `shard_map`: one (cluster, user) mesh, two
work splits, zero drift from the mesh shape.

The round itself is `repro.core.whfl.make_round_body`, the one body the
single engine runs too: its gates, hops, updates, counters, telemetry
and scopes.  This module supplies the `repro.core.whfl.Engine` parts
that differ on a mesh, the pad plan, and the `shard_map` around them.

Local training.  The per-user program is
`repro.core.whfl.make_local_train` (the same unit the single-device
engine vmaps); here every mesh shard `jax.lax.map`s it over its local
``(C_loc, M_loc)`` block of users.  `lax.map` runs the *identical*
per-slice program for every block size, so each user's delta is
bitwise the same no matter how many devices the users are spread over
(the established `batch="map"` property of the sweep engine, applied
to the user axis).

The hops.  The cluster hop with the ``fused`` backend is the scaling
path: every receiving IS hears every user, so the transmit symbols are
redistributed (all_to_all over symbols, all_gather over clusters) and
each shard runs the fused matched-filter combine for its ``C_loc`` rx
stations x ``N_loc`` symbols, passing its tile origin as the kernel's
counter bases (`rx_base`/`n_base`).  The counter PRNG keys on global
(rx, u, k, n) indices only, so every shard draws exactly the channels
the full-range call would have drawn — the hop is bitwise invariant to
mesh shape, and there is *no* cross-device reduction (the u/k folds
happen entirely in-kernel, in a mesh-independent block order).  On that
path users precode as they train and sum their own energies, which the
power fold gathers as a tiny ``[C, M]`` grid.  Every other hop gathers
the real ``[C, M, 2N]`` block and runs the single engine's own parts
on it, replicated: trivially mesh-invariant, and the single engine's
ops on the same values.

Uneven meshes — any mesh runs any scenario.  When the mesh does not
divide (C, M), the workload is padded up to the mesh with *inactive*
users and clusters (`repro.exec.mesh.pad_plan_for`): padded users
train on zero dummy shards (``lax.map`` skips nothing — the per-slice
program stays identical, so real users' deltas are untouched) but
their transmissions never exist — every hop, and the power accounting,
reads only the real ``[:C, :M]`` block, and the fused cluster hop
drops inactive rows so real users keep their *unpadded* global counter
indices (their h/z draws are exactly the single-engine draws; inactive
rx stations get zero-amplitude geometry rows and draw only at padded
rx counters).  The round body carries ``theta_IS`` in the padded Cp
rows and drops the inactive ones before the PS hop.  The result
extends the mesh-invariance theorem to all meshes: a padded sharded
run is bitwise invariant to the mesh shape for every scenario, and
bitwise identical to the unpadded single-engine ``batch="map"`` run —
final params, optimizer state, metrics and per-round power — for the
paper's scenarios, on both round drivers (tests/test_uneven_mesh.py
and tests/test_ft.py pin both).  Model state is bitwise cross-engine
everywhere; the one known exception is the scalar power metrics on
some odd fused-backend shapes, where XLA:CPU layout assignment rounds
the energy fold 1 ULP apart between the two programs (bounded by the
same tests).

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
from repro.core.channel import _cluster_geometry, _seed_words, resolve_backend
from repro.core.topology import Topology
from repro.core.whfl import (Engine, WHFLConfig, make_chunk_fn,
                             make_local_train, make_round_body)
from repro.exec.mesh import pad_plan_for
from repro.kernels import fused_mac, interpret_mode
# the executor's symbol padding must agree with the kernel's rounding
from repro.kernels.fused_mac import (_round_up, canonical_block_u,
                                     fused_mac_partials, fused_noise,
                                     fused_partials_reduce)
from repro.optim import Optimizer
from repro.sharding import shard_map


COMBINES = ("gathered", "u_sharded")


def _build_round(loss_fn: Callable, opt: Optimizer, topo: Topology,
                 cfg: WHFLConfig, spec: agg.FlatSpec, X, Y, mesh,
                 trace_counter: Optional[list] = None,
                 combine: str = "gathered"):
    """Construct the per-shard round shared by both sharded entry
    points: `make_sharded_round_fn` (one shard_map per round) and
    `make_sharded_chunk_fn` (a lax.scan of the same round *inside* one
    shard_map per eval window).  Returns ``(_round, X, Y)`` where
    `_round(state, key, P_t, P_is_t, X_loc, Y_loc)` is valid only
    inside a shard_map over ``("cluster", "user")``: the round body of
    `repro.core.whfl.make_round_body` over this shard's `Engine` parts.

    On a mesh that does not divide (C, M) the state's ``opt`` axes, the
    data shards and the per-shard layout use the padded (Cp, Mp) grid
    of `pad_plan_for` (see module docstring).

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
    N = spec.two_n // 2
    Np = _round_up(N, mu)       # symbol axis padded to split over 'user'
    N_loc = Np // mu
    local_train = make_local_train(loss_fn, opt, cfg)
    interpret = interpret_mode()
    body, parts = make_round_body(topo, cfg, spec, rows=Cp,
                                  trace_counter=trace_counter)

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
        if cfg.poison is not None:
            # the fault plan's user in the padded grid: real users keep
            # their (c, m), so each shard poisons its own tile of it
            pmask = np.zeros((Cp, Mp), bool)
            pmask[cfg.poison.c, cfg.poison.m] = True

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

    def _tile(x, ci, ui):
        """Padded [Cp, Mp, ...] -> this shard's [C_loc, M_loc, ...] block."""
        return jax.lax.dynamic_slice(
            x, (ci * C_loc, ui * M_loc) + (0,) * (x.ndim - 2),
            (C_loc, M_loc) + x.shape[2:])

    @jax.named_scope("whfl.train")
    def users_train(theta_IS, opt_loc, key, step, X_loc, Y_loc, ci, ui,
                    mult=None):
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

        `mult` (real [C, M], fused participation runs only): the round's
        COTAF transmit multipliers.  Each user's flat delta is precoded
        *before* its energy is computed inside the per-user map, the
        same elementwise multiply the single engine batches
        (`agg.cotaf_precode`), so precoded symbols AND energies stay
        bitwise cross-engine; padded slots carry multiplier 0 (a
        sampled-out user is exactly a pad slot).
        """
        keys = jax.random.split(key, C * M).reshape(C, M, 2)
        theta_loc = jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, ci * C_loc, C_loc, 0),
            theta_IS)
        xs = (theta_loc, opt_loc, X_loc, Y_loc,
              _tile(plan.pad_users(keys), ci, ui))
        if mult is not None:
            xs += (_tile(plan.pad_users(mult), ci, ui),)

        def one_cluster(args):
            th_c = args[0]

            def one_user(a):
                delta, st = local_train(th_c, a[0], a[1], a[2], a[3], step)
                flat = agg.flatten(spec, delta)
                if mult is not None:
                    flat = flat * a[4]
                with jax.named_scope("whfl.update"):   # power accounting
                    energy = agg.user_energy(flat)
                return flat, st, energy

            return jax.lax.map(one_user, args[1:])

        return jax.lax.map(one_cluster, xs)

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

    def fused_engine(X_loc, Y_loc, ci, ui):
        """`Engine` parts of the fused cluster hop: users precode and
        sum their energies as they train (``tx = sent = (flat_loc,
        pw_loc)``), the shard poisons its own tile, and the hop returns
        the replicated [Cp, 2N] estimate, inactive rows zero."""
        def train(theta_IS, opt_loc, key, step, mult):
            flat, opt_loc, pw = users_train(theta_IS, opt_loc, key, step,
                                            X_loc, Y_loc, ci, ui, mult)
            return (flat, pw), opt_loc

        def cluster(key, sent, step, claimed, P_t):
            flat_loc = sent[0]
            if cfg.poison is not None:
                hit = jnp.logical_and(step == cfg.poison.t,
                                      _tile(jnp.asarray(pmask), ci, ui))
                flat_loc = flat_loc + jnp.where(
                    hit, cfg.poison.value, 0.0)[..., None]
            if combine == "u_sharded":
                return fused_cluster_estimate_u_sharded(key, flat_loc, P_t,
                                                        ci, ui)
            return fused_cluster_estimate(key, flat_loc, P_t, ci, ui)

        def edge_power(sent, P_t):   # mesh-invariant `agg.symbol_power`
            return agg.symbol_power_from_energy(_gather_cm(sent[1]), P_t, N)

        return Engine(train=train, send=lambda tx, mult: tx,
                      cluster=cluster, edge_power=edge_power,
                      flat=lambda sent: _gather_cm(sent[0]))

    def gathered_engine(X_loc, Y_loc, ci, ui):
        """`Engine` parts of every other hop: the real [C, M, 2N] block,
        gathered as it is sent, then the single engine's own parts."""
        def train(theta_IS, opt_loc, key, step, mult):
            flat, opt_loc, _ = users_train(theta_IS, opt_loc, key, step,
                                           X_loc, Y_loc, ci, ui)
            return flat, opt_loc

        return parts._replace(
            train=train,
            send=lambda tx, mult: parts.send(_gather_cm(tx), mult))

    engine = fused_engine if fused_cluster_hop else gathered_engine

    def _round(state, key, P_t, P_is_t, X_loc, Y_loc):
        ci = jax.lax.axis_index("cluster")
        ui = jax.lax.axis_index("user")
        return body(engine(X_loc, Y_loc, ci, ui), state, key, P_t, P_is_t)

    return _round, X, Y


_USERS = P("cluster", "user")   # per-user data and optimizer state


def _state_spec(state) -> dict:
    """Every state leaf is replicated, but the per-user ``opt`` state,
    which is sharded over the (cluster, user) mesh."""
    return {k: _USERS if k == "opt" else P() for k in state}


def make_sharded_round_fn(loss_fn: Callable, opt: Optimizer, topo: Topology,
                          cfg: WHFLConfig, spec: agg.FlatSpec, X, Y, mesh,
                          trace_counter: Optional[list] = None,
                          combine: str = "gathered") -> Callable:
    """Build ``round_fn(state, key, P_t, P_is_t) -> state`` running one
    W-HFL round sharded over `mesh` (axes ``("cluster", "user")``).

    The round is `repro.core.whfl.make_round_body`'s, the single
    engine's too.  This engine supplies its `Engine` parts: users
    trained by a `lax.map` over each shard's block; for every hop but
    the fused cluster hop, their deltas gathered to the real block as
    they are sent and the single engine's parts from there; for the
    fused cluster hop, users that precode and sum their energies as
    they train, the sharded fused estimate and the energies' fold.

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
    _round, X, Y = _build_round(loss_fn, opt, topo, cfg, spec, X, Y, mesh,
                                trace_counter=trace_counter,
                                combine=combine)

    def round_fn(state, key, P_t, P_is_t):
        spec = _state_spec(state)
        sharded = shard_map(_round, mesh=mesh,
                            in_specs=(spec, P(), P(), P(), _USERS, _USERS),
                            out_specs=spec, check_vma=False)
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
    `lax.scan` *inside* one shard_map, so the host stops paying a
    shard_map re-entry + dispatch barrier per round.

    The scan is `repro.core.whfl.make_chunk_fn`'s over the `_round`
    the per-round entry point runs (the stepwise driver's key chain:
    ``key, sub = split(key)`` per round — threefry is integer-exact and
    replicated identically on every shard), so chunked sharded sweeps
    are bitwise equal to stepwise sharded sweeps AND retain the
    engine's bitwise mesh-invariance.  `eval_fn(state)` (optional) is
    folded into the same jitted program on the replicated post-window
    state, outside the shard_map.
    """
    _round, X, Y = _build_round(loss_fn, opt, topo, cfg, spec, X, Y, mesh,
                                trace_counter=trace_counter,
                                combine=combine)

    def _window(state, key, P_win, P_is_win, X_loc, Y_loc):
        window = make_chunk_fn(
            lambda st, k, P_t, P_is_t: _round(st, k, P_t, P_is_t, X_loc,
                                              Y_loc))
        state, key, _ = window(state, key, P_win, P_is_win)
        return state, key

    def chunk_fn(state, key, P_win, P_is_win):
        spec = _state_spec(state)
        sharded = shard_map(_window, mesh=mesh,
                            in_specs=(spec, P(), P(), P(), _USERS, _USERS),
                            out_specs=(spec, P()), check_vma=False)
        state, key = sharded(state, key,
                             jnp.asarray(P_win, jnp.float32),
                             jnp.asarray(P_is_win, jnp.float32), X, Y)
        metrics = None
        if eval_fn is not None:
            with jax.named_scope("whfl.eval"):
                metrics = eval_fn(state)
        return state, key, metrics

    return chunk_fn
