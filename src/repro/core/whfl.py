"""W-HFL federated trainer (paper §II-III protocol, Mode A: paper scale).

Per global round t:
  - every MU (c,m) runs `tau` local optimizer steps from its cluster
    model theta_IS[c]  (eq. 2),
  - each cluster OTA-aggregates the MU deltas at its IS (eqs. 8-13),
    repeated for `I` cluster iterations,
  - ISs OTA-transmit their accumulated deltas to the PS, which closes
    the round (eqs. 15-18).

The round is written once, in `make_round_body`, for both execution
engines: each supplies only its `Engine` parts (how users train and
send, the cluster-hop estimate, the edge-power fold).  The single
engine's round, `make_round_fn`, is one *pure* jitted function of
``(state, key, P_t, P_is_t)``; MU training is vmapped over (cluster,
user), and the round itself can be vmapped over a leading seed axis
(stacked states + per-seed keys) without re-tracing — this is what
`repro.sim.SweepRunner` does to run S seeds in one compilation.  The
sharded engine (`repro.exec.round`) runs the same body under
`shard_map`.  Baselines: `mode="conventional"` (single-hop OTA
FL, the paper's main comparison) and `OTAConfig(mode="ideal")`
(error-free).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core.channel import (ROBUST_CAPABLE_BACKENDS, OTAConfig,
                                cluster_ota, conventional_ota, global_ota,
                                orthogonal_cluster_ota, resolve_backend)
from repro.core.topology import Topology, power_schedule
from repro.fed.clients import ParticipationSchedule
from repro.obs.telemetry import (cluster_telemetry, edge_telemetry_init,
                                 is_telemetry, is_telemetry_zero,
                                 telemetry_init)
from repro.optim import Optimizer, apply_updates

if TYPE_CHECKING:   # annotation-only: repro.ft imports this layer
    from repro.ft.faults import GradPoison

CLUSTER_AGGREGATORS = ("mean", "median", "trimmed_mean")

# The round's phases, named on the compiled program's ops with
# `jax.named_scope` in both engines (metadata only: not one op
# changes).  A profile, or the `op_name` of the compiled HLO, then ties
# each device op to its phase: local training (the minibatch draw and
# lookups nested inside it as "whfl.batch"), the MU->IS cluster hop, the
# hop into the PS (IS->PS, or MU->PS in conventional mode), the model
# updates with the guard and power accounting, and the eval a chunk
# folds in.
SCOPES = ("whfl.train", "whfl.batch", "whfl.cluster_hop", "whfl.ps_hop",
          "whfl.update", "whfl.eval")


@dataclass(frozen=True)
class WHFLConfig:
    tau: int = 1                 # local (user) iterations per cluster round
    I: int = 1                   # cluster iterations per global round
    batch: int = 500
    mode: str = "whfl"           # "whfl" | "conventional"
    ota: OTAConfig = field(default_factory=OTAConfig)
    power_base: float = 1.0
    power_slope: float = 1e-2
    power_is_factor: float = 20.0
    power_low: bool = False      # P_t,low = 0.5 P_t (paper's I=1 runs)
    # per-round MU attendance + behavior (repro.fed.clients); the
    # default full schedule is an exact no-op (bitwise-identical round
    # program, pinned by tests/test_participation.py)
    participation: ParticipationSchedule = field(
        default_factory=ParticipationSchedule)
    # cluster-hop fold: "mean" (the paper's OTA superposition) |
    # "median" | "trimmed_mean" (robust folds over orthogonalized
    # per-user receptions; reference/equivalent/ideal only)
    cluster_agg: str = "mean"
    agg_trim: float = 0.25       # trim fraction for "trimmed_mean"
    # in-program round diagnostics (repro.obs.telemetry): when True the
    # state gains a "telemetry" block recomputed every round from
    # values the round already materializes.  The False default is a
    # PYTHON-level gate — the traced program is then literally the
    # pre-telemetry program (bitwise; same discipline as the
    # participation no-op above, pinned by tests/test_obs.py)
    telemetry: bool = False
    # non-finite guard over post-OTA estimates (repro.ft.guard):
    # "off" | "halt" | "skip_round" | "zero_fill".  "off" is the same
    # PYTHON-level gate as telemetry — the traced program is literally
    # the unguarded one (pinned by tests/test_ft.py)
    guard: str = "off"
    # deterministic fault injection (repro.ft.faults.GradPoison):
    # poison user (c, m)'s transmitted flat with NaN/Inf at round t.
    # None (default) inserts nothing (Python-level gate again)
    poison: Optional[GradPoison] = None


def validate_participation(cfg: WHFLConfig) -> None:
    """Fail fast on configs the trainer cannot build: unknown cluster
    aggregator, robust folds in conventional mode (there is no cluster
    hop to robustify), or robust folds on a superposition backend (see
    `repro.core.channel.ROBUST_CAPABLE_BACKENDS`)."""
    if cfg.cluster_agg not in CLUSTER_AGGREGATORS:
        raise ValueError(
            f"unknown cluster_agg {cfg.cluster_agg!r}; known: "
            f"{', '.join(CLUSTER_AGGREGATORS)}")
    if cfg.cluster_agg == "mean":
        return
    if cfg.mode != "whfl":
        raise ValueError(
            "robust cluster aggregation (cluster_agg="
            f"{cfg.cluster_agg!r}) needs the W-HFL cluster hop; "
            f"mode={cfg.mode!r} has none")
    if cfg.ota.mode != "ideal":
        backend = resolve_backend(cfg.ota)
        if backend not in ROBUST_CAPABLE_BACKENDS:
            raise ValueError(
                f"cluster_agg={cfg.cluster_agg!r} needs per-user "
                f"reception; backend {backend!r} is an in-channel OTA "
                f"superposition (see repro.core.channel."
                f"ROBUST_CAPABLE_BACKENDS)")


def init_round_state(params, opt: Optimizer, C: int, M: int,
                     telemetry_C: Optional[int] = None,
                     guard: bool = False):
    """Fresh per-run trainer state for `make_round_fn` round functions.

    ``telemetry_C`` (the REAL cluster count — not a mesh-padded one)
    adds the zeroed ``"telemetry"`` diagnostics block for
    ``WHFLConfig.telemetry=True`` round functions; leave it None for
    the default telemetry-off state, which is unchanged bitwise.
    ``guard=True`` (for ``WHFLConfig.guard != "off"`` round functions)
    adds the ``"guard_trips"`` int32 counter of non-finite guard trips
    (`repro.ft.guard`); the False default likewise changes nothing.
    """
    opt0 = opt.init(params)
    opt_state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (C, M) + x.shape).copy(), opt0)
    state = {
        "theta": params,
        "opt": opt_state,
        "t": jnp.zeros((), jnp.int32),
        "power_edge": jnp.zeros(()),   # sum of per-symbol tx power, edge
        "power_is": jnp.zeros(()),     # same, IS->PS hop
        "n_edge_tx": jnp.zeros(()),    # transmissions counted
        "n_is_tx": jnp.zeros(()),
    }
    if telemetry_C is not None:
        state["telemetry"] = telemetry_init(telemetry_C)
    if guard:
        state["guard_trips"] = jnp.zeros((), jnp.int32)
    return state


# Largest user shard whose minibatch labels are looked up densely.  On
# one TPU v5e (1.5 GHz), users vmapped as the single engine runs them:
# the label gather costs 8.7-13.5 ns an index (13-20 cycles); the dense
# pass costs 1.1-1.9 ps an element (0.0016-0.0028 cycles) at batch 500
# and 4.7 ps (0.0070 cycles) at batch 128.  Their ratio puts the
# crossover at 7,000-12,100 samples at batch 500 and 1,860-2,780 at
# batch 128; this sits below the smallest.
DENSE_LABELS_MAX_N = 1800


def label_lookup(n: int) -> str:
    """How `make_local_train` looks up a minibatch's labels in a user's
    shard of `n` samples: ``"dense"`` (`dense_labels`) up to
    `DENSE_LABELS_MAX_N`, else ``"gather"`` (``y[idx]``)."""
    return "dense" if n <= DENSE_LABELS_MAX_N else "gather"


def dense_labels(y, idx):
    """``y[idx]`` bit for bit, as one compare-and-select over the whole
    shard: exactly one position of ``y`` matches each index, so the sum
    adds one label to zeros.  A one-hot bfloat16 matvec on the MXU
    measured within 2% of it at batch 500 on a v5e, but is exact only
    for labels below 256; the select is exact for any integer."""
    hit = jnp.arange(y.shape[0], dtype=idx.dtype)[None, :] == idx[:, None]
    return jnp.sum(jnp.where(hit, y[None, :], 0), axis=-1, dtype=y.dtype)


def make_local_train(loss_fn: Callable, opt: Optimizer,
                     cfg: WHFLConfig) -> Callable:
    """Build one MU's local-training step ``local_train(theta,
    opt_state, x, y, key, step) -> (delta, opt_state)``: `cfg.tau`
    optimizer steps from `theta` on the user's shard, returning the
    model difference (eq. 2).

    Each step draws `cfg.batch` indices into the shard and takes those
    rows of `x` with a gather.  Their labels are looked up as
    `label_lookup` picks from the shard's size: a gather pays a fixed
    cost per index however narrow the row, so for a shard of up to
    `DENSE_LABELS_MAX_N` labels one dense compare-and-select over all of
    them (`dense_labels`) is cheaper; above it the gather is.  Both give
    the same integers, so the loss sees identical inputs either way.

    This per-user program is the unit both execution engines map over
    users — `make_round_fn` vmaps it over (cluster, user) on one
    device, `repro.exec` lax.maps it over each mesh shard's local
    users — so both engines train every user with the identical
    computation.
    """
    def local_train(theta, opt_state, x, y, key, step):
        def body(carry, k):
            th, st = carry
            kb, kd = jax.random.split(k)
            with jax.named_scope("whfl.batch"):
                idx = jax.random.randint(kb, (cfg.batch,), 0, x.shape[0])
                xb = x[idx]
                yb = (dense_labels(y, idx)
                      if label_lookup(y.shape[0]) == "dense" else y[idx])
            grads = jax.grad(loss_fn)(th, xb, yb, kd)
            upd, st = opt.update(grads, st, th, step)
            return (apply_updates(th, upd), st), None

        with jax.named_scope("whfl.train"):
            keys = jax.random.split(key, cfg.tau)
            (th, st), _ = jax.lax.scan(body, (theta, opt_state), keys)
            delta = jax.tree.map(lambda a, b: a - b, th, theta)
        return delta, st

    return local_train


class Engine(NamedTuple):
    """The parts of a W-HFL round that an execution engine supplies to
    the one round body (`make_round_body`), which runs everything else.

    ``tx`` and ``sent`` are the engine's own forms of what its users
    trained and what they transmitted; only these parts read them.

    - ``train(theta_IS, opt, key, step, mult) -> (tx, opt)``: local
      training of every user from its cluster's row of ``theta_IS``;
      ``mult`` is the round's COTAF multipliers ``[C, M]``, or None
      under full attendance.
    - ``send(tx, mult) -> sent``: hand the users' deltas to a hop, COTAF
      precoding included.
    - ``cluster(key, sent, step, claimed, P_t) -> est``: the cluster
      hop's estimate ``[rows, 2N]``, from the poison to the fold (the
      attendance rescale is the body's).
    - ``edge_power(sent, P_t)``: the transmissions' per-symbol power.
    - ``flat(sent)``: the real users' transmitted ``[C, M, 2N]`` symbols,
      which the MU->PS hop and the telemetry read.
    """
    train: Optional[Callable]
    send: Callable
    cluster: Callable
    edge_power: Callable
    flat: Callable


def make_round_body(topo: Topology, cfg: WHFLConfig, spec: agg.FlatSpec,
                    rows: Optional[int] = None,
                    trace_counter: Optional[list] = None):
    """The one W-HFL round both execution engines run.

    Returns ``(body, parts)``.  ``body(engine, state, key, P_t, P_is_t)
    -> state`` runs one round with an `Engine`'s parts: the
    participation, robustness, telemetry and fault-tolerance gates, the
    conventional MU->PS hop, the scan over `cfg.I` cluster iterations
    with its guard and telemetry carries, the IS->PS hop, the updates,
    the power and transmission counters and the telemetry block, each
    phase under its `SCOPES` name.  ``parts`` are the `Engine` parts of
    an engine whose users' deltas are the real ``[C, M, 2N]`` flat on
    every device, its ``train`` left for the engine to fill: precode,
    poison, the OTA or robust fold (`cluster_fold`), ``est`` padded to
    `rows`, and `agg.symbol_power`.

    `rows` is the number of cluster rows ``theta_IS`` is carried in:
    C (the default), or a mesh's padded Cp, whose extra rows the body
    drops before the PS hop and the telemetry.

    `trace_counter`, when given, is a list whose first element is
    incremented every time the round is *traced* (not executed) —
    tests use it to assert the one-compilation property of the sweep
    engine.
    """
    C, M = topo.C, topo.M
    rows = C if rows is None else rows

    # Participation / robustness gates are PYTHON-level: a full schedule
    # with the mean fold traces the literally identical round program as
    # before participation existed (no inserted ops), which is the
    # bitwise no-op guarantee tests/test_participation.py pins.
    validate_participation(cfg)
    schedule = cfg.participation
    partial = not schedule.is_full
    robust = cfg.cluster_agg != "mean"
    # the telemetry gate is Python-level too: with tele_on False not
    # one op below changes (repro.obs.telemetry; the fence-isolated
    # diagnostics are only *added*, never interleaved, when True)
    tele_on = cfg.telemetry
    # ... and so are the fault-tolerance gates (repro.ft): guard "off"
    # and poison None trace the literally identical program.  Deferred
    # import: repro.ft.guard sits above this layer (it pulls
    # repro.core.aggregation), so a module-level import would cycle.
    from repro.ft.guard import guard_estimate, validate_guard
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
        _pmask = jnp.asarray(_pmask)

    def maybe_poison(flat, step):
        """Inject the fault-plan's non-finite symbols into the fold
        input (the transmitted flat deltas) at the poisoned round —
        *after* power accounting reads `flat`, so injected energies
        match across engines.  Python-level no-op when poison is None.
        """
        if poison is None:
            return flat
        hit = jnp.logical_and(step == poison.t, _pmask)
        return flat + jnp.where(hit, poison.value, 0.0)[..., None]

    tx_base = jnp.asarray(schedule.tx_base(C, M)) if partial else None
    # receive weights the attendance rescale renormalizes over: the
    # ideal mean weighs users uniformly, the OTA folds by own-beta
    rx_w = (np.ones((C, M), np.float32) if cfg.ota.mode == "ideal"
            else np.asarray(topo.beta_own, np.float32))
    rx_w_conv = (np.ones((C, M), np.float32) if cfg.ota.mode == "ideal"
                 else np.asarray(topo.beta_mu_ps, np.float32))

    def real_rows(x):
        """[rows, ...] -> the real clusters' [C, ...] rows."""
        return x if rows == C else x[:C]

    def send(flat, mult):
        return agg.cotaf_precode(flat, mult) if partial else flat

    def cluster_fold(k2, flat, step, claimed, P_t):
        """Cluster-hop receive fold: the paper's OTA superposition mean
        or a robust masked fold over orthogonalized per-user receptions,
        its inactive rows (``rows > C``) exactly zero."""
        flat = maybe_poison(flat, step)
        if robust:
            mask = (claimed if partial
                    else jnp.ones((C, M), jnp.float32))
            per_user = orthogonal_cluster_ota(k2, flat, topo, P_t, cfg.ota)
            if cfg.cluster_agg == "median":
                est = agg.masked_median(per_user, mask)
            else:
                est = agg.masked_trimmed_mean(per_user, mask, cfg.agg_trim)
        else:
            est = cluster_ota(k2, flat, topo, P_t, cfg.ota)  # [C, 2N]
        if rows != C:
            est = jnp.pad(est, ((0, rows - C), (0, 0)))
        return est

    parts = Engine(train=None, send=send, cluster=cluster_fold,
                   edge_power=agg.symbol_power, flat=lambda flat: flat)

    def body(eng: Engine, state, key, P_t, P_is_t):
        if trace_counter is not None:
            trace_counter[0] += 1  # python side effect: runs at trace time
        theta = state["theta"]
        step = state["t"]
        if partial:
            claimed = schedule.present(step, C, M)
            mult = claimed * tx_base
        else:
            claimed = mult = None
        theta_IS = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (rows,) + x.shape), theta)

        if cfg.mode == "conventional":
            k1, k2 = jax.random.split(key)
            tx, opt_state = eng.train(theta_IS, state["opt"], k1, step,
                                      mult)
            with jax.named_scope("whfl.ps_hop"):
                sent = eng.send(tx, mult)
                flat = eng.flat(sent)
                est = conventional_ota(k2, maybe_poison(flat, step), topo,
                                       P_t, cfg.ota)
                if partial:
                    est = est * agg.attendance_rescale(
                        rx_w_conv.reshape(-1), claimed.reshape(-1))
            with jax.named_scope("whfl.update"):
                if guard_on:
                    est, g_trip = guard_estimate(est, cfg.guard)
                theta = apply_updates(theta, agg.unflatten(spec, est))
                p_edge = eng.edge_power(sent, P_t)
                out = {**state, "theta": theta, "opt": opt_state,
                       "t": step + 1,
                       "power_edge": state["power_edge"] + p_edge,
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
            tx, opt_state = eng.train(th_IS, opt_state, k1, step, mult)
            with jax.named_scope("whfl.cluster_hop"):
                sent = eng.send(tx, mult)
                est = eng.cluster(k2, sent, step, claimed, P_t)
                if partial and not robust:
                    resc = agg.attendance_rescale(rx_w, claimed)   # [C]
                    if rows != C:   # inactive rows stay exactly zero
                        resc = jnp.pad(resc, (0, rows - C),
                                       constant_values=1.0)
                    est = est * resc[:, None]
            with jax.named_scope("whfl.update"):
                if guard_on:
                    est, g_trip = guard_estimate(est, cfg.guard)
                    g_acc = g_acc + g_trip
                th_IS = jax.vmap(
                    lambda th, e: apply_updates(th, agg.unflatten(spec, e))
                )(th_IS, est)
                out = (th_IS, opt_state,
                       p_acc + eng.edge_power(sent, P_t))
                if guard_on:
                    out += (g_acc,)
            if tele_on:
                # the last cluster iteration's block survives
                out += (cluster_telemetry(eng.flat(sent), real_rows(est),
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
            is_deltas = jax.vmap(
                lambda th: agg.flatten(
                    spec, jax.tree.map(lambda a, b: a - b, th, theta)))(
                        jax.tree.map(real_rows, theta_IS))
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

    return body, parts


def make_round_fn(loss_fn: Callable, opt: Optimizer, topo: Topology,
                  cfg: WHFLConfig, spec: agg.FlatSpec, X, Y,
                  trace_counter: Optional[list] = None) -> Callable:
    """Build the single engine's pure per-round function
    ``round_fn(state, key, P_t, P_is_t) -> state``: the round body of
    `make_round_body` with its real-block `Engine` parts, and users
    trained in one vmap over ``(cluster, user)`` on this device.

    Everything static (data shards, topology geometry, config, flat
    spec) is closed over; the returned function touches no mutable
    state, so it can be wrapped in `jax.jit` once and additionally
    lifted with `jax.vmap` over a leading seed axis of ``(state, key)``
    — S seeds share one trace/compile.  `trace_counter`: see
    `make_round_body`.
    """
    C, M = topo.C, topo.M
    X = jnp.asarray(X)
    Y = jnp.asarray(Y)
    local_train = make_local_train(loss_fn, opt, cfg)
    body, parts = make_round_body(topo, cfg, spec,
                                  trace_counter=trace_counter)

    @jax.named_scope("whfl.train")
    def users_train(theta_IS, opt_state, key, step, mult):
        """theta_IS: [C]-stacked cluster models -> flat deltas [C,M,2N]."""
        keys = jax.random.split(key, C * M).reshape(C, M, 2)
        train_u = lambda th, st, x, y, k: local_train(th, st, x, y, k, step)
        train_c = jax.vmap(train_u, in_axes=(None, 0, 0, 0, 0))
        deltas, opt_state = jax.vmap(train_c)(theta_IS, opt_state, X, Y,
                                              keys)
        flat = jax.vmap(jax.vmap(lambda d: agg.flatten(spec, d)))(deltas)
        return flat, opt_state

    engine = parts._replace(train=users_train)

    def round_fn(state, key, P_t, P_is_t):
        return body(engine, state, key, P_t, P_is_t)

    return round_fn


def eval_windows(T: int, eval_every: int) -> list:
    """Partition ``T`` rounds into the stepwise driver's eval windows.

    The stepwise driver evaluates after round ``t`` whenever
    ``t % eval_every == 0 or t == T - 1``; the returned list holds the
    number of rounds between consecutive eval points (summing to T), so
    a chunked driver that scans one window per entry evaluates at
    exactly the stepwise rounds.  A non-divisible tail
    (``T % eval_every != 0``) simply yields a shorter final window —
    at most three distinct lengths ever occur (1, eval_every, tail),
    which bounds the number of chunk compilations.
    """
    e = max(1, int(eval_every))
    out, prev = [], -1
    for t in range(T):
        if t % e == 0 or t == T - 1:
            out.append(t - prev)
            prev = t
    return out


def make_chunk_fn(round_fn: Callable, eval_fn: Optional[Callable] = None,
                  split_fn: Optional[Callable] = None) -> Callable:
    """Lift a pure round executor into a device-resident multi-round
    chunk: ``chunk_fn(state, keys, P_win, P_is_win) -> (state, keys,
    metrics)`` runs ``len(P_win)`` rounds in ONE ``lax.scan`` dispatch.

    `round_fn` may be per-seed (``(state, key, P, P_is) -> state``) or
    already seed-batched (e.g. ``lax.map``/``vmap`` over a stacked seed
    axis); `split_fn` must match — the default `jax.random.split` for
    a single ``[2]`` key, ``jax.vmap(jax.random.split)`` for stacked
    ``[S, 2]`` keys.  The scan body reproduces the stepwise driver's
    per-round computation exactly: split the carried key(s) into
    ``(next_key, sub)`` (threefry is integer-exact under any batching)
    and apply `round_fn` to the sub-key with that round's precomputed
    power values (``P_win``/``P_is_win``, from
    `repro.core.topology.power_schedule` on a ``[T]`` index array).

    Bitwise note (pinned by `tests/test_driver.py`): the scan must sit
    *outside* the seed batching — scanning a per-seed round inside a
    ``lax.map`` slice lets XLA:CPU fuse across the round boundary and
    drift by ~1 ULP, whereas a scan whose body IS the stepwise batched
    program (split + ``lax.map``'d round) reproduces it bitwise.  Pass
    the batched round + batched split here and lift nothing afterwards.

    `eval_fn(state) -> metrics` (optional, same batching level as
    `round_fn`) folds the eval into the same compiled program, emitted
    once per window; the host loop becomes one dispatch per eval window
    instead of 2-3 dispatches per round.
    """
    split_fn = jax.random.split if split_fn is None else split_fn

    def chunk_fn(state, keys, P_win, P_is_win):
        def body(carry, Ps):
            st, ks = carry
            s2 = split_fn(ks)          # [..., 2, 2]: (next_key, sub)
            st = round_fn(st, s2[..., 1, :], Ps[0], Ps[1])
            return (st, s2[..., 0, :]), None

        (state, keys), _ = jax.lax.scan(body, (state, keys),
                                        (P_win, P_is_win))
        metrics = None
        if eval_fn is not None:
            with jax.named_scope("whfl.eval"):
                metrics = eval_fn(state)
        return state, keys, metrics

    return chunk_fn


class WHFLTrainer:
    """loss_fn(params, xb, yb, rng) -> scalar; data X/Y: [C, M, n, ...].

    Thin stateful wrapper over `make_round_fn`: owns the jitted round
    and the power schedule.  `round_fn` (available after `init_state`)
    is the underlying pure function, for callers that batch it
    themselves (see `repro.sim.sweep`).
    """

    def __init__(self, loss_fn: Callable, local_opt: Optimizer,
                 topo: Topology, cfg: WHFLConfig, X: np.ndarray,
                 Y: np.ndarray):
        self.loss_fn = loss_fn
        self.opt = local_opt
        self.topo = topo
        self.cfg = cfg
        self.X = jnp.asarray(X)
        self.Y = jnp.asarray(Y)
        self.C, self.M = topo.C, topo.M
        self._spec = None
        self.round_fn: Optional[Callable] = None
        self._round = None

    # -- state ---------------------------------------------------------------

    def init_state(self, params):
        spec = agg.make_flat_spec(params)
        if spec != self._spec:  # (re)build on first use or new model shape
            self._spec = spec
            self.round_fn = make_round_fn(self.loss_fn, self.opt, self.topo,
                                          self.cfg, spec, self.X, self.Y)
            self._round = jax.jit(self.round_fn)
        return init_round_state(
            params, self.opt, self.C, self.M,
            telemetry_C=self.C if self.cfg.telemetry else None,
            guard=self.cfg.guard != "off")

    # -- public API ------------------------------------------------------------

    def round(self, state, key):
        t = int(state["t"])
        P_t, P_is_t = power_schedule(
            t, self.cfg.power_base, self.cfg.power_slope,
            self.cfg.power_is_factor, self.cfg.power_low)
        return self._round(state, key, P_t, P_is_t)

    def avg_edge_power(self, state) -> float:
        n = float(state["n_edge_tx"])
        return float(state["power_edge"]) / max(n, 1.0)

    def avg_is_power(self, state) -> float:
        n = float(state["n_is_tx"])
        return float(state["power_is"]) / max(n, 1.0)


# Jitted per-apply_fn eval cores: the per-batch Python loop used to call
# `apply_fn` untraced every batch of every eval, which dominated sweep
# wall-clock between rounds.  One trace per (apply_fn, batch shape) now
# covers every call; the final short batch is padded + masked so it
# shares the same trace.
_ACCURACY_JIT_CACHE: dict = {}


def accuracy(apply_fn, params, X, Y, batch: int = 2000) -> float:
    n = len(X)
    if n == 0:
        return 0.0
    count = _ACCURACY_JIT_CACHE.get(apply_fn)
    if count is None:
        def _count(p, xb, yb, mask):
            logits = apply_fn(p, xb)
            hit = (jnp.argmax(logits, -1) == yb) & mask
            return jnp.sum(hit.astype(jnp.int32))

        count = _ACCURACY_JIT_CACHE[apply_fn] = jax.jit(_count)
    X, Y = jnp.asarray(X), jnp.asarray(Y)
    batch = min(batch, n)
    correct = 0
    for i in range(0, n, batch):
        xb, yb = X[i:i + batch], Y[i:i + batch]
        m = xb.shape[0]
        if m < batch:
            pad = batch - m
            xb = jnp.concatenate(
                [xb, jnp.zeros((pad,) + xb.shape[1:], xb.dtype)])
            yb = jnp.concatenate([yb, jnp.zeros((pad,), yb.dtype)])
        mask = jnp.arange(batch) < m
        correct += int(count(params, xb, yb, mask))
    return correct / n
