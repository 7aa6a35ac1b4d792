"""Batched multi-seed scenario sweeps.

`SweepRunner` executes ``S seeds x M scenarios`` as M batched
computations: per scenario, the per-seed trainer states are stacked
along a leading axis and the pure W-HFL round function
(`repro.core.whfl.make_round_fn`) is lifted with ``jax.vmap`` over
``(state, key)`` — one jit trace/compile covers the whole seed batch,
and per-seed trajectories are exactly the trajectories of S sequential
single-seed runs (every random draw depends only on the per-seed key).

Heterogeneous configs (different models, I, topologies) cannot share a
trace, so scenarios are looped; homogeneous seeds are vmapped.

    PYTHONPATH=src python -m repro.sim.sweep \
        --scenarios fig2_iid,fig2_noniid --seeds 5 --out results/sweep.json

`--exec sharded --mesh 2x4` swaps the single-device round for the
mesh-sharded engine (`repro.exec.ShardedSweepRunner` — shard_map over
a (cluster, user) device mesh, bitwise invariant to the mesh shape;
meshes that do not divide (C, M) pad inactive users in, so any mesh
runs any scenario);
`--driver chunked` swaps the per-round host loop for the
device-resident chunked driver (`lax.scan` per eval window, donated
carry buffers, async metric fetch — bitwise equal to stepwise under
``--batch map``); `--bench-out` additionally writes the
``BENCH_sweep.json`` throughput trajectory (rounds/sec per scenario +
engine/driver metadata); `--telemetry` records the in-program
physical-layer diagnostics block (`repro.obs.telemetry` — off by
default, and off is a bitwise no-op), `--trace` journals the run as
`repro.obs.trace/v1` JSONL, and `--profile DIR` wraps the sweep in
``jax.profiler.trace``.  On the profiler's clock the drivers mark their
host work as spans: ``sweep.dispatch`` (each chunk or round call),
``sweep.fetch`` (metric fetches), ``sweep.checkpoint`` (each save) and
``sweep.guard`` (each guard check); the round program names its phases
(`repro.core.whfl.SCOPES`).

Output is a structured JSON document (`SCHEMA_VERSION`), and
`csv_lines` renders the benchmark-suite CSV convention
(``name,us_per_call,derived``) from the same records.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import time
import warnings
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core.topology import power_schedule
from repro.core.whfl import (eval_windows, init_round_state, label_lookup,
                             make_chunk_fn, make_round_fn)
from repro.ft import ckpt as ft_ckpt
from repro.ft.faults import FaultPlan, hard_crash
from repro.ft.guard import GUARD_POLICIES, validate_guard
from repro.nn.core import split_params
from repro.obs.telemetry import TELEMETRY_KEYS, summarize
from repro.optim import adam, sgd
from repro.sim.compile_cache import enable_compile_cache
from repro.sim.scenario import Scenario, get_scenario, list_scenarios


@contextlib.contextmanager
def _silence_cpu_donation_warnings():
    """CPU backends ignore `donate_argnums` (donation is a TPU/GPU
    memory optimization) and warn once per chunk compilation; silence
    exactly that message, scoped to the chunked drive, and ONLY on CPU
    — on TPU/GPU an unusable-donation warning is the signal that the
    memory optimization silently failed to apply, and must surface."""
    with warnings.catch_warnings():
        if jax.default_backend() == "cpu":
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        yield

SCHEMA_VERSION = "repro.sim.sweep/v1"
BENCH_SCHEMA_VERSION = "repro.bench.sweep/v1"
STATE_SCHEMA_VERSION = "repro.sim.state/v1"

# Round drivers: how the host loop feeds rounds to the device.
#   "stepwise" — one dispatch per round (+ key-split + eval dispatches),
#     host recomputes the power schedule per round; the historical
#     behaviour and the bitwise reference.
#   "chunked"  — `repro.core.whfl.make_chunk_fn`: lax.scan over each
#     eval window, precomputed [T] power schedule, eval folded into the
#     scanned program, carried buffers donated, metrics fetched
#     asynchronously (one device sync per scenario).  Bitwise identical
#     to "stepwise" per round in the "map" batch mode.
DRIVERS = ("stepwise", "chunked")

# Every per-scenario record carries exactly these keys (tests pin them).
# "telemetry" is null unless the sweep ran with telemetry=True (the
# record key is always present so the schema stays fixed-shape).
RECORD_KEYS = ("scenario", "seeds", "rounds", "metrics", "final",
               "n_traces", "seconds", "exec", "telemetry")
METRIC_KEYS = ("acc", "loss", "edge_power", "is_power")


@dataclass
class SweepResult:
    """One scenario x seed-batch: trajectories are [S][n_evals] lists."""
    scenario: Scenario
    seeds: List[int]
    rounds: List[int]                 # global-round index of each eval
    acc: List[List[float]]
    loss: List[List[float]]
    edge_power: List[List[float]]     # running avg per-symbol edge power
    is_power: List[List[float]]
    n_traces: int                     # jit traces of the round function
    seconds: float
    exec_info: Dict = field(default_factory=dict)
    # field-major telemetry trajectories {key: [S][n_evals](scalar|[C])}
    # — populated iff the scenario ran with cfg.telemetry=True
    telemetry: Optional[Dict] = field(default=None, repr=False)
    final_state: Optional[dict] = field(default=None, repr=False)

    def to_record(self) -> Dict:
        fin = {
            "acc_mean": float(np.mean([a[-1] for a in self.acc])),
            "acc_std": float(np.std([a[-1] for a in self.acc])),
            "loss_mean": float(np.mean([l[-1] for l in self.loss])),
            "edge_power": float(np.mean([p[-1] for p in self.edge_power])),
            "is_power": float(np.mean([p[-1] for p in self.is_power])),
        }
        return {
            "scenario": self.scenario.to_json(),
            "seeds": list(self.seeds),
            "rounds": list(self.rounds),
            "metrics": {"acc": self.acc, "loss": self.loss,
                        "edge_power": self.edge_power,
                        "is_power": self.is_power},
            "final": fin,
            "n_traces": self.n_traces,
            "seconds": self.seconds,
            "exec": dict(self.exec_info),
            "telemetry": self.telemetry,
        }


def _jit_with_data(build, X, Y, donate_argnums=()):
    """``jax.jit`` of the program ``build(X, Y)`` returns, with the data
    shards X, Y bound as leading arguments: closed over, they would be
    compiled into the executable as constants (hundreds of MB at paper
    size); as arguments they stay device buffers.  The result has the
    program's own signature; `donate_argnums` index into it."""
    fn = jax.jit(lambda X, Y, *args: build(X, Y)(*args),
                 donate_argnums=tuple(i + 2 for i in donate_argnums))
    return functools.partial(fn, jnp.asarray(X), jnp.asarray(Y))


class _FTContext:
    """Per-scenario fault-tolerance driving context (repro.ft), handed
    to the round drivers: where to resume from, when to checkpoint,
    which faults to inject, and how to check the non-finite guard.
    With every feature off (the default) the drivers consult only
    cheap attribute reads — no device syncs, no saved state, no
    behavior change."""

    def __init__(self, guard_on: bool = False, guard_halt: bool = False,
                 ckpt=None, ckpt_every: int = 1, start_round: int = 0,
                 windows_done: int = 0, faults=None, save=None,
                 check_guard=None):
        self.guard_on = guard_on
        self.guard_halt = guard_halt
        self.ckpt = ckpt                   # CheckpointManager or None
        self.ckpt_every = ckpt_every
        self.start_round = start_round     # rounds already completed
        self.windows_done = windows_done   # eval windows already done
        self.faults = faults               # FaultPlan or None
        self.save = save                   # save(state, keys, cursor)
        self.check_guard = check_guard     # check_guard(state, round)
        self.halted = False                # guard policy "halt" fired
        self.trips = 0                     # cumulative guard trips


class SweepRunner:
    """Run a list of scenarios over a shared seed batch.

    scenarios: Scenario objects or registry names.
    seeds: int S (-> seeds 0..S-1) or explicit list.
    quick: substitute each scenario's CI-sized `.quick()` variant.
    batch: how the seed axis is executed — both are ONE trace/compile:
      - "vmap": seeds run data-parallel (SIMD over the seed axis);
        fastest, but batched-dot lowering differs from the unbatched
        round, so per-seed results can drift from a standalone run by
        float-rounding ULPs.
      - "map": seeds run through `jax.lax.map`, whose scan body is the
        *identical* per-slice computation for every batch size — a
        sweep slice is bitwise equal to the same seed swept alone
        (adding seeds never perturbs existing trajectories).
    """

    def __init__(self, scenarios: Sequence[Union[str, Scenario]],
                 seeds: Union[int, Sequence[int]] = 1,
                 quick: bool = False, keep_state: bool = False,
                 batch: str = "vmap", driver: str = "stepwise",
                 warmup: bool = False, telemetry: bool = False,
                 trace=None, checkpoint: Optional[str] = None,
                 ckpt_every: int = 1, resume: bool = False,
                 guard: str = "off",
                 faults: Optional[FaultPlan] = None):
        self.scenarios = [get_scenario(s) if isinstance(s, str) else s
                          for s in scenarios]
        if quick:
            self.scenarios = [s.quick() for s in self.scenarios]
        # telemetry=True rewrites the scenario configs themselves, so
        # records carry the flag and `whfl_config()` turns the gate on
        if telemetry:
            self.scenarios = [replace(s, telemetry=True)
                              for s in self.scenarios]
        self.telemetry = telemetry
        # optional repro.obs.trace.TraceWriter (duck-typed: anything
        # with .emit(event, **fields)); None disables journaling
        self.trace = trace
        self.seeds = (list(range(seeds)) if isinstance(seeds, int)
                      else list(seeds))
        self.quick = quick
        self.keep_state = keep_state
        if batch not in ("vmap", "map"):
            raise ValueError(f"batch must be 'vmap' or 'map', got {batch!r}")
        self.batch = batch
        if driver not in DRIVERS:
            raise ValueError(f"driver must be one of {DRIVERS}, "
                             f"got {driver!r}")
        self.driver = driver
        # warmup=True pre-executes every compiled program on throwaway
        # copies before the timed driving loop, so `drive_seconds`
        # (and BENCH_sweep rounds/sec) measure steady-state dispatch +
        # execution, not trace/compile time.
        self.warmup = warmup
        # fault tolerance (repro.ft): checkpoint dir (per-scenario
        # subdirs of saved sweep carries + resume manifests), save
        # cadence in eval windows, resume-if-present, non-finite guard
        # policy, and the deterministic fault-injection plan.  The
        # defaults (None/off) are Python-level no-ops: not one op of
        # the driven programs, and not one line of the driving loop's
        # timing-relevant path, changes (pinned by tests/test_ft.py).
        self.checkpoint = checkpoint
        if ckpt_every < 1:
            raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
        self.ckpt_every = ckpt_every
        if resume and checkpoint is None:
            raise ValueError("resume=True needs a checkpoint directory")
        self.resume = resume
        validate_guard(guard)
        self.guard = guard
        self.faults = faults

    def _emit(self, event: str, **fields) -> None:
        """Journal one `repro.obs.trace` event (no-op without --trace)."""
        if self.trace is not None:
            self.trace.emit(event, **fields)

    def _note_traces(self, counter, seen: List[int]) -> None:
        """Journal a ``compile`` event when the trace counter moved
        since the last call (i.e. a program was (re)traced)."""
        if counter[0] > seen[0]:
            self._emit("compile", n_traces=counter[0],
                       new=counter[0] - seen[0])
            seen[0] = counter[0]

    # -- engine hooks (overridden by repro.exec.ShardedSweepRunner) ---------

    def _init_states(self, params, opt, topo, cfg):
        """Per-seed initial round states.  Engine hook: the sharded
        engine sizes the per-user ``opt`` axes to its mesh's padded
        (Cp, Mp) grid when the mesh does not divide (C, M)."""
        tele_C = topo.C if cfg.telemetry else None
        return [init_round_state(p, opt, topo.C, topo.M,
                                 telemetry_C=tele_C,
                                 guard=cfg.guard != "off")
                for p in params]

    def _finalize_state(self, state, topo):
        """The state view stored as ``final_state`` AND written into
        checkpoints.  Engine hook: the sharded engine strips
        inactive-user padding here, so cross-engine final states
        compare tree-equal and checkpoints are mesh-portable."""
        return state

    def _restore_state(self, state, topo):
        """Inverse of `_finalize_state` for ``--resume``: lift a
        canonical checkpointed state back into this engine's layout.
        Engine hook: the sharded engine re-pads the opt axes to its
        mesh's (Cp, Mp) grid."""
        return state

    def _build_round(self, sc: Scenario, loss_fn, opt, topo, cfg, spec,
                     X, Y, counter):
        """Build the seed-batched round executor
        ``(states, keys, P_t, P_is_t) -> states`` for one scenario."""
        return _jit_with_data(
            lambda X, Y: self._batch_round_fn(make_round_fn(
                loss_fn, opt, topo, cfg, spec, X, Y, trace_counter=counter)),
            X, Y)

    def _batch_round_fn(self, round_fn):
        """Seed-batched round executor, unjitted (see class doc for
        vmap vs map) — reused as the scan body of the chunked driver,
        where it must appear exactly as the stepwise program."""
        if self.batch == "vmap":
            return jax.vmap(round_fn, in_axes=(0, 0, None, None))
        return lambda st, ks, P, P_is: jax.lax.map(
            lambda a: round_fn(a[0], a[1], P, P_is), (st, ks))

    def _batch_round(self, round_fn):
        """Lift a per-seed round over the stacked seed axis — one
        trace/compile either way."""
        return jax.jit(self._batch_round_fn(round_fn))

    def _batch_eval_fn(self, eval_fn):
        """Seed-batched per-state eval, unjitted; in map mode the
        per-slice program is identical for every batch size (the same
        bitwise property as `_batch_round_fn`)."""
        if self.batch == "vmap":
            return jax.vmap(eval_fn)
        return lambda state: jax.lax.map(eval_fn, state)

    def _build_chunk(self, sc: Scenario, loss_fn, opt, topo, cfg, spec,
                     X, Y, counter, eval_fn):
        """Build the seed-batched chunk executor ``(states, keys, P_win,
        P_is_win) -> (states, keys, metrics)`` for one scenario
        (chunked driver).  The scan sits OUTSIDE the seed batching —
        its body is the exact stepwise batched program (see
        `make_chunk_fn` for why this is what keeps it bitwise) — and
        the jit donates the carried (state, keys) buffers: for the
        [S]-stacked states of the scale_u* scenarios the round state is
        the dominant allocation, and donation lets XLA reuse it across
        eval windows instead of holding two copies live."""
        def chunk(X, Y):
            round_fn = make_round_fn(loss_fn, opt, topo, cfg, spec, X, Y,
                                     trace_counter=counter)
            return make_chunk_fn(self._batch_round_fn(round_fn),
                                 self._batch_eval_fn(eval_fn),
                                 split_fn=jax.vmap(jax.random.split))

        return _jit_with_data(chunk, X, Y, donate_argnums=(0, 1))

    def _exec_info(self, topo=None, two_n=None, n_user=None) -> Dict:
        """Execution-engine metadata recorded with every result.
        `device_count` is the number of devices the engine *uses* (not
        how many are visible): always 1 for the single-device engine.
        `topo`/`two_n` (when given) let engines record
        workload-dependent metadata — the sharded engine reports its
        padded shape and per-device peak symbol-block bytes.
        `n_user` (samples in a user's shard) adds the minibatch label
        lookup local training uses (`repro.core.whfl.label_lookup`)."""
        info = {"name": "single", "mesh": None,
                "device_count": 1, "batch": self.batch}
        if n_user is not None:
            info["label_lookup"] = label_lookup(n_user)
        return info

    # -- one scenario, all seeds at once ------------------------------------

    def run_scenario(self, sc: Scenario) -> SweepResult:
        t0 = time.perf_counter()
        init_fn, apply_fn, loss_fn = sc.task_fns()
        X, Y, xte, yte = sc.make_data()
        n_user = X.shape[2]
        topo = sc.make_topology()
        cfg = sc.whfl_config()
        # runner-level fault-tolerance knobs rewrite the round config:
        # both are Python-level gates in the round builders, so the
        # defaults leave the traced programs untouched
        if self.guard != "off":
            cfg = replace(cfg, guard=self.guard)
        if self.faults is not None and self.faults.poison is not None:
            cfg = replace(cfg, poison=self.faults.poison)
        opt = adam(sc.lr) if sc.opt == "adam" else sgd(sc.lr)
        self._emit("scenario_start", scenario=sc.name,
                   seeds=len(self.seeds), rounds=sc.rounds,
                   driver=self.driver, telemetry=cfg.telemetry,
                   exec_info=self._exec_info(topo, n_user=n_user))

        # Stacked per-seed state: identical-by-construction to S
        # independent `init_state` calls.
        params = [split_params(init_fn(jax.random.PRNGKey(s)))[0]
                  for s in self.seeds]
        spec = agg.make_flat_spec(params[0])
        counter = [0]
        states = self._init_states(params, opt, topo, cfg)
        state = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        keys = jnp.stack([jax.random.PRNGKey(s + 1) for s in self.seeds])

        xte_j, yte_j = jnp.asarray(xte), jnp.asarray(yte)

        def _eval(theta):
            logits = apply_fn(theta, xte_j)
            acc = jnp.mean((jnp.argmax(logits, -1) == yte_j)
                           .astype(jnp.float32))
            onehot = jax.nn.one_hot(yte_j, logits.shape[-1])
            loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot,
                                     -1))
            return acc, loss

        S, T = len(self.seeds), sc.rounds
        rounds: List[int] = []
        acc_t = [[] for _ in range(S)]
        loss_t = [[] for _ in range(S)]
        pe_t = [[] for _ in range(S)]
        pi_t = [[] for _ in range(S)]
        tele_acc: List[Dict] = []     # one telemetry pytree per eval

        def record(accs, losses, pe, pi, tele=None):
            for s in range(S):
                acc_t[s].append(float(accs[s]))
                loss_t[s].append(float(losses[s]))
                pe_t[s].append(float(pe[s]))
                pi_t[s].append(float(pi[s]))
            if tele is not None:
                tele_acc.append(tele)

        # -- fault tolerance: checkpoint manager, resume, guard hooks --
        guard_on = cfg.guard != "off"
        ckpt_mgr = None
        if self.checkpoint is not None:
            ckpt_mgr = ft_ckpt.CheckpointManager(
                os.path.join(self.checkpoint, sc.name),
                faults=self.faults,
                emit=lambda ev, **f: self._emit(ev, scenario=sc.name,
                                                **f))
        fingerprint = ft_ckpt.scenario_fingerprint(sc.to_json())
        start_round, windows_done = 0, 0
        if self.resume and ckpt_mgr is not None:
            # the checkpoint payload is the CANONICAL (pad-stripped)
            # carry, so the validation template is the finalized view
            # of a fresh state — mesh-portable by construction
            template = {"state": self._finalize_state(state, topo),
                        "keys": keys}

            def _check(man):
                ft_ckpt.check_manifest(man, fingerprint, self.seeds, T,
                                       jax.__version__)
                if man.get("guard", "off") != cfg.guard:
                    raise ValueError(
                        f"checkpoint was cut with guard="
                        f"{man.get('guard')!r}, this run uses "
                        f"{cfg.guard!r}")

            loaded = ckpt_mgr.load_latest(template, check=_check)
            if loaded is not None:
                payload, man = loaded
                state = self._restore_state(
                    jax.tree.map(jnp.asarray, payload["state"]), topo)
                keys = jnp.asarray(payload["keys"])
                start_round = int(man["round"])
                ev = man["eval"]
                rounds.extend(int(r) for r in ev["rounds"])
                for s in range(S):
                    acc_t[s].extend(ev["metrics"]["acc"][s])
                    loss_t[s].extend(ev["metrics"]["loss"][s])
                    pe_t[s].extend(ev["metrics"]["edge_power"][s])
                    pi_t[s].extend(ev["metrics"]["is_power"][s])
                if ev.get("telemetry"):
                    tele_acc.extend(
                        {k: np.asarray(v) for k, v in t.items()}
                        for t in ev["telemetry"])
                windows_done = len(ev["rounds"])
                self._emit("checkpoint", scenario=sc.name, resumed=True,
                           round=start_round, windows=windows_done)

        git_sha = ft_ckpt.git_sha() if ckpt_mgr is not None else None

        def save_ckpt(state_now, keys_now, cursor):
            manifest = {
                "scenario": sc.name, "fingerprint": fingerprint,
                "seeds": list(self.seeds), "round": int(cursor),
                "rounds_total": int(T), "git_sha": git_sha,
                "jax_version": jax.__version__,
                "engine": {**self._exec_info(topo, n_user=n_user),
                           "driver": self.driver},
                "guard": cfg.guard, "telemetry": bool(cfg.telemetry),
                "eval": {
                    "rounds": [int(r) for r in rounds],
                    "metrics": {"acc": [list(a) for a in acc_t],
                                "loss": [list(v) for v in loss_t],
                                "edge_power": [list(p) for p in pe_t],
                                "is_power": [list(p) for p in pi_t]},
                    # host accumulators ride the JSON manifest (floats
                    # round-trip exactly), the device carry the npz
                    "telemetry": ([{k: np.asarray(t[k]).tolist()
                                    for k in t} for t in tele_acc]
                                  if cfg.telemetry else None),
                },
            }
            ckpt_mgr.save(
                int(cursor),
                {"state": self._finalize_state(state_now, topo),
                 "keys": keys_now}, manifest)

        ft = _FTContext(guard_on=guard_on,
                        guard_halt=cfg.guard == "halt", ckpt=ckpt_mgr,
                        ckpt_every=self.ckpt_every,
                        start_round=start_round,
                        windows_done=windows_done, faults=self.faults,
                        save=jax.profiler.annotate_function(
                            save_ckpt, name="sweep.checkpoint"))

        def check_guard(state_now, round_idx):
            total = int(np.sum(np.asarray(state_now["guard_trips"])))
            if total > ft.trips:
                ft.trips = total
                self._emit("guard", scenario=sc.name, round=round_idx,
                           trips=total, policy=cfg.guard)
            if ft.guard_halt and total > 0:
                ft.halted = True

        ft.check_guard = jax.profiler.annotate_function(
            check_guard, name="sweep.guard")

        if self.driver == "chunked":
            state, dispatches, drive_s = self._drive_chunked(
                sc, loss_fn, opt, topo, cfg, spec, X, Y, counter, _eval,
                state, keys, T, rounds, record, ft)
        else:
            state, dispatches, drive_s = self._drive_stepwise(
                sc, loss_fn, opt, topo, cfg, spec, X, Y, counter, _eval,
                state, keys, T, rounds, record, ft)

        # field-major [S][n_evals] trajectories; per-eval leaves are
        # scalars or [C] lists, same layout as the metrics block
        telemetry = None
        if tele_acc:
            telemetry = {
                k: [[np.asarray(t[k][s]).tolist() for t in tele_acc]
                    for s in range(S)]
                for k in TELEMETRY_KEYS}
            for rd, t in zip(rounds, tele_acc):
                self._emit("telemetry", scenario=sc.name, round=rd,
                           summary=summarize(t))

        exec_info = {**self._exec_info(topo, two_n=spec.two_n,
                                       n_user=n_user),
                     "driver": self.driver,
                     "dispatches": dispatches, "drive_seconds": drive_s,
                     "warmup": self.warmup}
        if guard_on:
            ft.check_guard(state, rounds[-1] if rounds else start_round)
            exec_info.update(guard=cfg.guard, guard_trips=ft.trips,
                             guard_halted=ft.halted)
        if ckpt_mgr is not None:
            exec_info.update(
                ckpt_saves=ckpt_mgr.saves,
                ckpt_io_retries=ckpt_mgr.io_retries,
                ckpt_save_seconds=round(ckpt_mgr.save_seconds, 6),
                ckpt_load_seconds=round(ckpt_mgr.load_seconds, 6),
                ckpt_every=self.ckpt_every,
                resumed_from=start_round if self.resume else None)
        seconds = time.perf_counter() - t0
        self._emit("scenario_end", scenario=sc.name, seconds=seconds,
                   drive_seconds=drive_s, dispatches=dispatches,
                   n_traces=counter[0],
                   final_acc_mean=float(np.mean([a[-1] for a in acc_t])))
        return SweepResult(
            scenario=sc, seeds=self.seeds, rounds=rounds, acc=acc_t,
            loss=loss_t, edge_power=pe_t, is_power=pi_t,
            n_traces=counter[0], seconds=seconds,
            exec_info=exec_info, telemetry=telemetry,
            final_state=(self._finalize_state(state, topo)
                         if self.keep_state else None))

    # -- the stepwise driver: one dispatch per round ------------------------

    def _drive_stepwise(self, sc, loss_fn, opt, topo, cfg, spec, X, Y,
                        counter, _eval, state, keys, T, rounds, record,
                        ft):
        round_b = self._build_round(sc, loss_fn, opt, topo, cfg, spec, X, Y,
                                    counter)
        split_b = jax.jit(jax.vmap(jax.random.split))
        if self.batch == "vmap":
            eval_b = jax.jit(jax.vmap(_eval))
        else:  # same per-slice program for every batch size (bitwise)
            eval_b = jax.jit(lambda th: jax.lax.map(_eval, th))

        if self.warmup:  # compile + run every program on throwaway copies
            P0, P_is0 = power_schedule(
                0, cfg.power_base, cfg.power_slope, cfg.power_is_factor,
                cfg.power_low)
            ks = split_b(keys)
            jax.block_until_ready(
                (round_b(jax.tree.map(jnp.copy, state), ks[:, 1], P0,
                         P_is0),
                 eval_b(state["theta"])))

        tele_on = cfg.telemetry
        dispatches = 0
        seen = [counter[0]]
        t_drive = time.perf_counter()
        win_t0, win_rounds = t_drive, 0
        windows_done = ft.windows_done
        for t in range(ft.start_round, T):
            P_t, P_is_t = power_schedule(
                t, cfg.power_base, cfg.power_slope, cfg.power_is_factor,
                cfg.power_low)
            with jax.profiler.TraceAnnotation("sweep.dispatch"):
                ks = split_b(keys)
                keys, subs = ks[:, 0], ks[:, 1]
                state = round_b(state, subs, P_t, P_is_t)
            dispatches += 2
            win_rounds += 1
            if t % sc.eval_every == 0 or t == T - 1:
                with jax.profiler.TraceAnnotation("sweep.fetch"):
                    accs, losses = eval_b(state["theta"])
                    accs, losses = np.asarray(accs), np.asarray(losses)
                    pe = np.asarray(state["power_edge"]
                                    / jnp.maximum(state["n_edge_tx"], 1.0))
                    pi = np.asarray(state["power_is"]
                                    / jnp.maximum(state["n_is_tx"], 1.0))
                    tele = (jax.device_get(state["telemetry"]) if tele_on
                            else None)
                dispatches += 1
                rounds.append(t + 1)
                record(accs, losses, pe, pi, tele)
                self._note_traces(counter, seen)
                now = time.perf_counter()
                self._emit("window", scenario=sc.name, round=t + 1,
                           rounds=win_rounds,
                           seconds=round(now - win_t0, 6))
                win_t0, win_rounds = now, 0
                windows_done += 1
                if ft.guard_on:
                    ft.check_guard(state, t + 1)
                due = (ft.ckpt is not None
                       and (windows_done % ft.ckpt_every == 0
                            or t == T - 1 or ft.halted))
                if due:
                    ft.save(state, keys, t + 1)
                if ft.halted:
                    break
                if (ft.faults is not None
                        and ft.faults.crash_window == windows_done):
                    self._emit("fault", scenario=sc.name,
                               kind="crash_window", window=windows_done)
                    hard_crash(f"injected crash after window "
                               f"{windows_done} ({sc.name})")
            # crash_round fires AFTER any boundary checkpoint at t+1,
            # so a resume from that checkpoint replays nothing
            if (ft.faults is not None
                    and ft.faults.crash_round == t + 1):
                self._emit("fault", scenario=sc.name,
                           kind="crash_round", round=t + 1)
                hard_crash(f"injected crash after round {t + 1} "
                           f"({sc.name})")
        jax.block_until_ready(state)
        return state, dispatches, time.perf_counter() - t_drive

    # -- the chunked driver: one dispatch per eval window -------------------

    def _drive_chunked(self, sc, loss_fn, opt, topo, cfg, spec, X, Y,
                       counter, _eval, state, keys, T, rounds, record,
                       ft):
        """Device-resident multi-round driving: `lax.scan` over each
        eval window (`repro.core.whfl.make_chunk_fn`), a precomputed
        [T] power schedule, donated carry buffers, and asynchronous
        metric fetch — every window is enqueued without a host sync,
        and ONE `device_get` at the end transfers all metrics.

        Fault tolerance forces a drain of the pending metric fetches
        at each boundary that needs host state (a due checkpoint, a
        guard-halt check, an injected crash) — off-path, the program
        and its one-sync-per-scenario schedule are untouched."""
        tele_on = cfg.telemetry   # Python-level: off-path programs are
                                  # byte-identical to pre-telemetry ones

        def eval_state(st):   # per-seed metrics, folded into the chunk
            acc, loss = _eval(st["theta"])
            pe = st["power_edge"] / jnp.maximum(st["n_edge_tx"], 1.0)
            pi = st["power_is"] / jnp.maximum(st["n_is_tx"], 1.0)
            if tele_on:   # ride the same async fetch as the metrics
                return acc, loss, pe, pi, st["telemetry"]
            return acc, loss, pe, pi

        chunk_b = self._build_chunk(sc, loss_fn, opt, topo, cfg, spec, X, Y,
                                    counter, eval_state)
        # the [T]-vectorized schedule is bit-identical (after the f32
        # cast at the jit boundary) to the per-round scalars the
        # stepwise driver feeds — see core.topology.power_schedule
        P_all, P_is_all = power_schedule(
            np.arange(T), cfg.power_base, cfg.power_slope,
            cfg.power_is_factor, cfg.power_low)
        P_all = P_all.astype(np.float32)
        P_is_all = P_is_all.astype(np.float32)

        windows = eval_windows(T, sc.eval_every)
        # checkpoints are cut at window boundaries, so a resume cursor
        # must land exactly on a prefix of the window schedule
        skip, done = 0, 0
        while done < ft.start_round and skip < len(windows):
            done += windows[skip]
            skip += 1
        if done != ft.start_round:
            raise ValueError(
                f"resume round {ft.start_round} is not an eval-window "
                f"boundary of T={T}, eval_every={sc.eval_every}")
        with _silence_cpu_donation_warnings():
            if self.warmup:  # compile + run each distinct window once
                for w in sorted(set(windows)):
                    jax.block_until_ready(chunk_b(
                        jax.tree.map(jnp.copy, state), jnp.copy(keys),
                        P_all[:w], P_is_all[:w]))

            seen = [counter[0]]
            t_drive = time.perf_counter()
            pending, off = [], ft.start_round
            windows_done, driven = skip, 0

            def drain():
                nonlocal pending
                with jax.profiler.TraceAnnotation("sweep.fetch"):
                    for metrics in jax.device_get(pending):
                        record(*metrics)
                pending = []

            for w in windows[skip:]:
                w_t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("sweep.dispatch"):
                    state, keys, metrics = chunk_b(state, keys,
                                                   P_all[off:off + w],
                                                   P_is_all[off:off + w])
                off += w
                rounds.append(off)
                pending.append(metrics)
                driven += 1
                windows_done += 1
                self._note_traces(counter, seen)
                # the host's side of the window only: this driver is
                # async by design (one device sync per scenario), so
                # execution time is not observable per window.  Where
                # the runtime's queue of executions is full, the call
                # waits for room, so this includes that wait; a
                # --profile trace puts the call's `sweep.dispatch` span
                # beside the device's ops, which shows how much it was
                self._emit("window", scenario=sc.name, round=off,
                           rounds=w, enqueue_only=True,
                           seconds=round(time.perf_counter() - w_t0, 6))
                due_ckpt = (ft.ckpt is not None
                            and (windows_done % ft.ckpt_every == 0
                                 or off == T))
                crash_due = (ft.faults is not None
                             and (ft.faults.crash_window == windows_done
                                  or (ft.faults.crash_round is not None
                                      and off >= ft.faults.crash_round)))
                if ft.guard_halt or due_ckpt or crash_due:
                    drain()   # manifests and guard reads need host state
                    if ft.guard_on:
                        ft.check_guard(state, off)
                    if due_ckpt or (ft.halted and ft.ckpt is not None):
                        ft.save(state, keys, off)
                    if ft.halted:
                        break
                    if crash_due:
                        kind = ("crash_window"
                                if ft.faults.crash_window == windows_done
                                else "crash_round")
                        self._emit("fault", scenario=sc.name, kind=kind,
                                   window=windows_done, round=off)
                        hard_crash(f"injected crash after window "
                                   f"{windows_done} / round {off} "
                                   f"({sc.name})")
            # one sync: block on the last chunk, then transfer every
            # window's metrics (all already resident on device)
            drain()
        return state, driven, time.perf_counter() - t_drive

    # -- the sweep -----------------------------------------------------------

    def run(self) -> List[SweepResult]:
        return [self.run_scenario(sc) for sc in self.scenarios]


def sweep_to_json(results: Sequence[SweepResult],
                  quick: bool = False) -> Dict:
    return {
        "schema": SCHEMA_VERSION,
        "quick": quick,
        "scenarios": [r.to_record() for r in results],
    }


def bench_doc(results: Sequence[SweepResult]) -> Dict:
    """``BENCH_sweep.json``: the throughput trajectory (rounds/sec per
    scenario, with the execution-engine + round-driver metadata that
    produced it).  ``rounds_per_sec`` is computed from the driving-loop
    wall time (``drive_seconds``) so it measures dispatch + execution;
    with ``warmup`` runs it excludes trace/compile too.  ``seconds``
    stays the total scenario wall clock (setup + compile + drive)."""
    records = []
    for r in results:
        rounds = r.rounds[-1] if r.rounds else 0
        ds = r.exec_info.get("drive_seconds")
        # `is None`, not falsy: a legitimate 0.0 drive time must not
        # silently fall back to the compile-inclusive total
        drive_s = float(r.seconds if ds is None else ds)
        records.append({
            "scenario": r.scenario.name,
            "seeds": len(r.seeds),
            "rounds": rounds,
            "seconds": r.seconds,
            "drive_seconds": drive_s,
            "rounds_per_sec": (rounds / drive_s) if drive_s > 0 else 0.0,
            "driver": r.exec_info.get("driver", "stepwise"),
            "dispatches": r.exec_info.get("dispatches"),
            "exec": dict(r.exec_info),
        })
    return {"schema": BENCH_SCHEMA_VERSION,
            "jax_backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "records": records}


def state_doc(results: Sequence[SweepResult]) -> Dict:
    """``--state-out``: the full final carry of every scenario as JSON
    (`STATE_SCHEMA_VERSION`), leaf-keyed by `jax.tree_util.keystr` with
    exact float round-trips — diffable with ``repro.obs.diff
    --max-ulp 0``, which is how CI gates kill+resume runs bitwise
    against an uninterrupted reference."""
    scenarios = []
    for r in results:
        if r.final_state is None:
            raise ValueError(
                f"no final state for {r.scenario.name!r}: state_doc "
                f"needs keep_state=True")
        leaves, _ = jax.tree_util.tree_flatten_with_path(r.final_state)
        scenarios.append({
            "scenario": r.scenario.name,
            "state": {jax.tree_util.keystr(path):
                      np.asarray(v).tolist() for path, v in leaves},
        })
    return {"schema": STATE_SCHEMA_VERSION, "scenarios": scenarios}


def csv_lines(doc: Dict, prefix: str = "sweep") -> List[str]:
    """Benchmark-suite CSV convention: name,us_per_call,derived."""
    lines = []
    for rec in doc["scenarios"]:
        name = rec["scenario"]["name"]
        n_rounds = max(rec["rounds"][-1] if rec["rounds"] else 1, 1)
        us = 1e6 * rec["seconds"] / n_rounds
        fin = rec["final"]
        lines.append(
            f"{prefix}/{name},{us:.1f},"
            f"final_acc={fin['acc_mean']:.3f}"
            f"±{fin['acc_std']:.3f};edge_power={fin['edge_power']:.2e};"
            f"seeds={len(rec['seeds'])};traces={rec['n_traces']}")
    return lines


def main(argv: Optional[Sequence[str]] = None) -> Dict:
    ap = argparse.ArgumentParser(
        description="Batched multi-seed scenario sweep")
    ap.add_argument("--scenarios", default="fig2_iid",
                    help="comma-separated registry names (--list to see)")
    ap.add_argument("--seeds", type=int, default=1,
                    help="number of seeds (0..S-1), vmapped per scenario")
    ap.add_argument("--seed-list", default=None,
                    help="explicit comma-separated seeds (overrides --seeds)")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized scenario variants (seconds, not hours)")
    ap.add_argument("--batch", default="vmap", choices=["vmap", "map"],
                    help="seed-axis execution: vmap (fastest) or map "
                         "(bitwise-reproducible per seed)")
    ap.add_argument("--driver", default="stepwise",
                    help="round driver(s), comma-separated subset of "
                         "{stepwise, chunked}: stepwise = one dispatch "
                         "per round; chunked = lax.scan per eval window "
                         "(device-resident, donated buffers, async "
                         "metric fetch; bitwise == stepwise under "
                         "--batch map).  Listing both runs both and "
                         "records each, e.g. for driver comparisons in "
                         "--bench-out")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile + pre-run every program on "
                         "throwaway copies so recorded rounds/sec "
                         "measure steady-state dispatch+execution "
                         "rather than compile time")
    ap.add_argument("--exec", default="single", dest="exec_name",
                    choices=["single", "sharded"],
                    help="execution engine: single (one device) or sharded "
                         "(shard_map over a --mesh device mesh; bitwise "
                         "mesh-invariant, forces --batch map)")
    ap.add_argument("--mesh", default="1x1",
                    help="device mesh CxU for --exec sharded, e.g. 2x4 "
                         "(clusters x users-per-cluster shards); the "
                         "axes need NOT divide the scenario's (C, M) — "
                         "inactive users are padded in with amp = w = 0 "
                         "and the run stays bitwise identical to the "
                         "single-engine run (so e.g. fig2's M=5 runs on "
                         "2x4); on CPU force host devices with "
                         "XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--combine", default="gathered",
                    choices=["gathered", "u_sharded"],
                    help="fused cluster-hop distribution for --exec "
                         "sharded: gathered (default) all-gathers the "
                         "full [U, N_loc] symbol block per device; "
                         "u_sharded keeps each cluster-shard's own user "
                         "tile, runs the partial-combine kernel and "
                         "folds per-tile accumulators in pinned global "
                         "u-block order — bitwise equal to gathered and "
                         "to the single engine, O(U/mc) symbol memory")
    ap.add_argument("--telemetry", action="store_true",
                    help="compute the in-program per-round diagnostics "
                         "block (repro.obs.telemetry: per-hop SNR, noise "
                         "floor, grad-norm ratio, attendance, symbol "
                         "energies) and record its per-eval trajectories; "
                         "off (the default) the compiled programs are "
                         "bitwise identical to a build without the "
                         "feature")
    ap.add_argument("--trace", default=None, metavar="OUT_JSONL",
                    help="write a structured JSONL run journal "
                         "(repro.obs.trace/v1 events: compiles, per-"
                         "window timings, telemetry summaries) here")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the sweep in jax.profiler.trace(DIR) "
                         "(view with TensorBoard / xprof); host spans "
                         "sweep.dispatch / fetch / checkpoint / guard "
                         "and the round's whfl.* scopes name its time")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="checkpoint the full sweep carry (stacked "
                         "trainer states, opt state, PRNG keys, metric "
                         "accumulators) into per-scenario subdirs of DIR "
                         "at eval-window boundaries (repro.ft.ckpt/v1 "
                         "manifest + atomic npz); off (the default) is a "
                         "Python-level no-op")
    ap.add_argument("--ckpt-every", type=int, default=1, metavar="W",
                    help="checkpoint cadence in eval windows (default 1; "
                         "the final window is always saved)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint under "
                         "--checkpoint if one exists (fresh start "
                         "otherwise); the resumed trajectory is bitwise "
                         "identical to the uninterrupted run")
    ap.add_argument("--guard", default="off",
                    choices=list(GUARD_POLICIES),
                    help="non-finite guard on post-OTA aggregated "
                         "estimates: off (default; bitwise no-op) | halt "
                         "(zero the estimate, stop the scenario at the "
                         "next eval boundary) | skip_round (drop the "
                         "poisoned update, keep going) | zero_fill "
                         "(zero only the non-finite entries)")
    ap.add_argument("--inject", default=None, metavar="SPEC",
                    help="deterministic fault injection "
                         "(repro.ft.faults.FaultPlan), e.g. "
                         "'crash_round=5', 'crash_window=2', "
                         "'save_errors=2', 'poison=nan@4:0:1' "
                         "(MODE@round:cluster:user), comma-combinable; "
                         "injected crashes exit with status 173")
    ap.add_argument("--out", default=None, help="write JSON document here")
    ap.add_argument("--state-out", default=None, metavar="PATH",
                    help="write the full final carry of every scenario "
                         "as JSON (repro.sim.state/v1; implies keeping "
                         "final states) — diffable bitwise with "
                         "repro.obs.diff --max-ulp 0")
    ap.add_argument("--bench-out", default=None,
                    help="write the BENCH_sweep.json throughput document "
                         "(rounds/sec per scenario) here")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name, sc in sorted(list_scenarios().items()):
            ota = sc.ota_mode + (f"[{sc.ota_backend}]" if sc.ota_backend
                                 else "")
            print(f"{name:28s} {sc.dataset}/{sc.partition} "
                  f"tau={sc.tau} I={sc.I} mode={sc.mode}/{ota}")
        return {}

    seeds = ([int(s) for s in args.seed_list.split(",")]
             if args.seed_list else args.seeds)
    faults = None
    if args.inject:
        try:
            faults = FaultPlan.parse(args.inject)
        except ValueError as e:
            ap.error(str(e))
    if args.checkpoint and len(args.driver.split(",")) > 1:
        ap.error("--checkpoint needs a single --driver (the round "
                 "cursor keys one driving schedule)")
    # checkpoint-knob validation happens HERE, not downstream: a knob
    # that silently does nothing (e.g. --ckpt-every 5 with no
    # --checkpoint dir) is a run the user believes is protected and
    # isn't
    if args.ckpt_every < 1:
        ap.error(f"--ckpt-every must be >= 1 windows, "
                 f"got {args.ckpt_every}")
    if args.resume and not args.checkpoint:
        ap.error("--resume needs --checkpoint DIR (nowhere to resume "
                 "from)")
    if args.ckpt_every != 1 and not args.checkpoint:
        ap.error("--ckpt-every needs --checkpoint DIR (no checkpoints "
                 "are being cut)")
    tracer = None
    if args.trace:
        from repro.obs.trace import TraceWriter   # lazy: obs layer
        tracer = TraceWriter(args.trace)
    profile_cm = (jax.profiler.trace(args.profile) if args.profile
                  else contextlib.nullcontext())
    results = []
    # close the journal even when a scenario raises mid-sweep: the
    # partial journal ends with run_end and stays machine-readable
    # (repro.obs.trace.validate_trace --allow-truncated-tail)
    try:
        with profile_cm:
            for driver in args.driver.split(","):
                try:
                    # lazy import: repro.exec builds on this module
                    from repro.exec import make_runner
                    runner = make_runner(args.exec_name,
                                         args.scenarios.split(","),
                                         seeds=seeds, quick=args.quick,
                                         batch=args.batch,
                                         mesh=args.mesh,
                                         driver=driver.strip(),
                                         warmup=args.warmup,
                                         telemetry=args.telemetry,
                                         trace=tracer,
                                         keep_state=bool(args.state_out),
                                         checkpoint=args.checkpoint,
                                         ckpt_every=args.ckpt_every,
                                         resume=args.resume,
                                         guard=args.guard, faults=faults,
                                         combine=args.combine)
                except (KeyError, ValueError) as e:
                    ap.error(str(e.args[0] if e.args else e))
                enable_compile_cache()
                results.extend(runner.run())
    finally:
        if tracer is not None:
            tracer.close()
            print("wrote", args.trace)
    doc = sweep_to_json(results, quick=args.quick)
    for line in csv_lines(doc):
        print(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
        print("wrote", args.out)
    if args.state_out:
        os.makedirs(os.path.dirname(args.state_out) or ".",
                    exist_ok=True)
        with open(args.state_out, "w") as f:
            json.dump(state_doc(results), f, indent=1)
        print("wrote", args.state_out)
    if args.bench_out:
        os.makedirs(os.path.dirname(args.bench_out) or ".", exist_ok=True)
        with open(args.bench_out, "w") as f:
            json.dump(bench_doc(results), f, indent=1)
        print("wrote", args.bench_out)
    return doc


if __name__ == "__main__":
    main()
