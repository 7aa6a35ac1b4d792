"""One benchmark run of one cell: set-up, the measured window, the
reference comparison and the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a
configuration (``bench/configs/<config>.json``: the model, the network
and the protocol, with its plain reference model in
``bench/models/<model>.py``) under a traffic mix
(``bench/workloads/<traffic>.json``: channel, seeds per dispatch,
rounds per eval window, engine and mesh).  The limits that decide
``correct`` are in ``bench/limits/<cell>.json`` and the per-layer
metric readers in ``bench/metrics/<metric>.py``.  Everything is found
by name, so a new cell, configuration, mix or metric is a new file.

The run drives the program the way ``repro.sim.sweep.SweepRunner``
drives a scenario with the chunked driver: the runner's own
``_init_states`` and ``_build_chunk`` hooks build the stacked seed
states and the compiled chunk program (eval folded in); the host feeds
it the power schedule one eval window at a time.  Only the
single-device engine is driven so far.

Set-up makes the inputs from the seed, builds that one chunk program
with its state, and drives it through the first three rounds with the
window's own call and feed, keeping what the reference comparison
needs.  The window continues the same state.  It dispatches eval
windows without waiting on them, as the sweep's chunked driver
enqueues every window without a sync; the TPU runtime queues a few
dozen executions and then makes the next dispatch wait, so the chip
stays fed through a short stall of the host.  The host fetches the
metrics of the oldest windows in batches of BATCH, once LAG more are
dispatched behind them, so that a fetch finds its windows done and
never drains the chip's queue.  When ``seconds`` have passed it
dispatches nothing more, fetches all it sent, and only then reads the
clock.  After the window
the program's state is freed and the plain reference
(``bench/reference.py``) replays the first three rounds from the same
inputs.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core import aggregation as agg  # noqa: E402
from repro.core.topology import Topology, power_schedule  # noqa: E402
from repro.optim import adam, sgd  # noqa: E402
from repro.sim import compile_cache  # noqa: E402
from repro.sim.scenario import Scenario  # noqa: E402
from repro.sim.sweep import SweepRunner  # noqa: E402

from bench import compare, trace  # noqa: E402
from bench.inputs import make_inputs, model  # noqa: E402
from bench.reference import Round, Setup  # noqa: E402

CHECK_ROUNDS = 3
LAG = 64        # windows dispatched behind the oldest one not yet fetched
BATCH = 32      # windows fetched in one transfer


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list          # BENCHMARK.json per_layer entries it reports
    end_to_end: list


def find_cell(name: str) -> Cell:
    bm = load_json(ROOT, "BENCHMARK.json")
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{', '.join(w['name'] for w in bm['workloads'])}")

    def reports(metric):
        return name in metric.get("workloads", [name])

    return Cell(name=name, chips=int(wl["chips"]),
                config=load_json(BENCH, "configs", wl["config"] + ".json"),
                traffic=load_json(BENCH, "workloads", wl["traffic"] + ".json"),
                limits=load_json(BENCH, "limits", name + ".json"),
                per_layer=[m for m in bm["per_layer"] if reports(m)],
                end_to_end=[m for m in bm["end_to_end"] if reports(m)])


def metric_reader(name: str):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """Seconds spent tracing, lowering and compiling, and persistent
    cache hits, read from JAX's monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
        if event == self.EVENTS[-1]:
            self.backend_compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def enable_cache() -> None:
    """JAX's persistent compilation cache at the program's fixed path
    inside the checkout (`repro.sim.compile_cache.DEFAULT_DIR`), for
    every program however fast it compiles.  A cache directory set in
    the environment is not taken: it could lie outside the checkout."""
    jax.config.update("jax_compilation_cache_dir", compile_cache.DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def scenario(cell: Cell) -> Scenario:
    cfg, tr = cell.config, cell.traffic
    return Scenario(
        name=cell.name, dataset=cfg["dataset"], partition="iid",
        tau=cfg["tau"], I=cfg["I"], batch=cfg["batch"], mode="whfl",
        ota_mode=tr["ota_mode"], ota_backend=tr["ota_backend"],
        C=cfg["C"], M=cfg["M"], K=cfg["K"], K_ps=cfg["K_ps"],
        sigma_z2=cfg["sigma_z2"], lr=cfg["lr"], opt=cfg["opt"],
        n_train=cfg["n_train"], n_test=cfg["n_test"],
        eval_every=tr["rounds_per_window"])


def topology(cfg: dict, inp) -> Topology:
    return Topology(C=cfg["C"], M=cfg["M"], K=cfg["K"], K_ps=cfg["K_ps"],
                    p=cfg["path_loss"], sigma_h2=cfg["sigma_h2"],
                    sigma_z2=cfg["sigma_z2"], d_mu_is=inp.d_mu_is,
                    d_is_ps=inp.d_is_ps, d_mu_ps=inp.d_mu_ps)


def runner_for(cell: Cell, seeds):
    """The single-device sweep engine with the chunked driver; the
    traffic's `batch` ("vmap", the default, or "map") batches the seeds."""
    if cell.traffic["engine"] != "single":
        raise ValueError(f"{cell.name}: engine {cell.traffic['engine']!r} "
                         f"is not supported by the harness yet")
    return SweepRunner([], seeds=seeds, driver="chunked",
                       batch=cell.traffic.get("batch", "vmap"))


class ProgramFault:
    """A fault planted in the program underneath the harness, for the
    tests that show `correct` comes out false; the base is no fault."""

    def config(self, cfg):
        return cfg

    def test_set(self, xte, yte):
        return xte, yte

    def chunk(self, chunk):
        return chunk


@dataclass
class Program:
    """The compiled chunk program with its carried state and keys."""
    chunk: object
    state: dict
    keys: object
    cfg: object
    rounds_done: int = 0
    enqueue_s: float = 0.0

    def feed(self, w: int):
        P, P_is = power_schedule(
            np.arange(self.rounds_done, self.rounds_done + w),
            self.cfg.power_base, self.cfg.power_slope,
            self.cfg.power_is_factor, self.cfg.power_low)
        self.rounds_done += w
        return P.astype(np.float32), P_is.astype(np.float32)

    def dispatch(self, w: int):
        """Enqueue one eval window of `w` rounds; returns its metrics
        (device arrays, not waited for)."""
        P, P_is = self.feed(w)
        t0 = time.perf_counter()
        self.state, self.keys, metrics = self.chunk(self.state, self.keys,
                                                    P, P_is)
        self.enqueue_s += time.perf_counter() - t0
        return metrics


def eval_state_fn(apply_fn, xte, yte):
    """The per-seed eval the chunk program folds in: a copy of
    `SweepRunner.run_scenario`'s `_eval` and `_drive_chunked`'s
    `eval_state`, which the sweep keeps as closures (a test holds the
    copy to the sweep's results)."""
    def eval_state(st):
        logits = apply_fn(st["theta"], xte)
        acc = jnp.mean((jnp.argmax(logits, -1) == yte).astype(jnp.float32))
        onehot = jax.nn.one_hot(yte, logits.shape[-1])
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        pe = st["power_edge"] / jnp.maximum(st["n_edge_tx"], 1.0)
        pi = st["power_is"] / jnp.maximum(st["n_is_tx"], 1.0)
        return acc, loss, pe, pi
    return eval_state


def build_program(cell: Cell, inp, seed: int, fault=None) -> Program:
    """The program's chunk and stacked seed states, from the runner's
    own hooks (`SweepRunner.run_scenario` / `_drive_chunked`)."""
    sc = scenario(cell)
    S = cell.traffic["seeds_per_dispatch"]
    runner = runner_for(cell, list(range(seed, seed + S)))
    _, apply_fn, loss_fn = sc.task_fns()
    fault = fault or ProgramFault()
    cfg = fault.config(sc.whfl_config())
    opt = adam(sc.lr) if sc.opt == "adam" else sgd(sc.lr)
    topo = topology(cell.config, inp)
    params = [jax.tree.map(lambda a: a[s], inp.params) for s in range(S)]
    spec = agg.make_flat_spec(params[0])
    states = runner._init_states(params, opt, topo, cfg)
    state = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    xte, yte = fault.test_set(inp.xte, inp.yte)
    chunk = fault.chunk(runner._build_chunk(sc, loss_fn, opt, topo, cfg,
                                            spec, inp.X, inp.Y, [0],
                                            eval_state_fn(apply_fn, xte,
                                                          yte)))
    return Program(chunk=chunk, state=state, keys=inp.keys, cfg=cfg)


@dataclass
class Window:
    seconds: float = 0.0
    windows: int = 0
    rounds: int = 0
    failed: int = 0
    compiles: int = 0
    enqueue_s: float = 0.0    # host time inside the chunk calls


def drive_window(prog: Program, seconds: float, w: int, log: CompileLog,
                 span=None) -> Window:
    """The measured window (see module doc)."""
    span = span or (lambda name: contextlib.nullcontext())
    res = Window()
    c0 = log.backend_compiles
    inflight = deque()

    def fetch(n):
        with span("bench.fetch"):
            got = jax.device_get([inflight.popleft() for _ in range(n)])
        for acc, loss, pe, pi in got:
            if not np.all(np.isfinite(loss)):
                res.failed += 1

    r0, e0 = prog.rounds_done, prog.enqueue_s
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            with span("bench.dispatch"):
                inflight.append(prog.dispatch(w))
            res.windows += 1
            if len(inflight) >= LAG + BATCH:
                fetch(BATCH)
            if time.perf_counter() - t0 >= seconds:
                break
        fetch(len(inflight))
        res.seconds = time.perf_counter() - t0
    res.rounds = prog.rounds_done - r0
    res.enqueue_s = prog.enqueue_s - e0
    res.compiles = log.backend_compiles - c0
    return res


def setup_rounds(prog: Program, adam_opt: bool):
    """The first CHECK_ROUNDS rounds through the window's own call:
    each round's eval losses, the optimizer's first moments after round
    1, and theta after the last."""
    losses, m1 = [], None
    for r in range(CHECK_ROUNDS):
        acc, loss, pe, pi = jax.device_get(prog.dispatch(1))
        losses.append(np.asarray(loss))
        if r == 0 and adam_opt:
            m1 = jax.device_get(prog.state["opt"]["m"])
    return np.stack(losses), m1, jax.device_get(prog.state["theta"])


def reference_readings(cell: Cell, seed: int, prog_out: dict,
                       dtype=jnp.float32) -> dict:
    """Compare the program's first rounds with the plain reference."""
    cfg, tr = cell.config, cell.traffic
    S = tr["seeds_per_dispatch"]
    inp = make_inputs(seed, cfg, S)
    setup = Setup.from_config(cfg, tr, inp.d_mu_is, inp.d_is_ps)
    rnd = Round(setup, model(cfg), dtype)
    ref = []
    for s in range(S):
        theta0 = jax.tree.map(lambda a: a[s], inp.params)
        ref.append(rnd.run(theta0, inp.X, inp.Y, inp.xte, inp.yte,
                           inp.keys[s], CHECK_ROUNDS))
    theta0 = jax.device_get(inp.params)
    del inp
    return compare.readings(prog_out, ref, theta0, cfg["opt"] == "adam")


def device_info(chips: int) -> dict:
    d = jax.devices()[0]
    peak = 0
    for dev in jax.devices()[:chips]:
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": peak}


@dataclass
class Context:
    """What a per-layer metric reader may read."""
    cell: Cell
    window: Window
    compile_s: float
    seeds: int
    peaks: dict
    events: dict = field(default_factory=dict)


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, fault=None) -> dict:
    """One run of `cell` (a `Cell`, or a workload name to look up).
    `fault` (tests only) breaks the program underneath."""
    if isinstance(cell, str):
        cell = find_cell(cell)
    log = CompileLog()
    S = cell.traffic["seeds_per_dispatch"]
    w = cell.traffic["rounds_per_window"]
    timing = {"start_s": time.perf_counter() - t_start}
    inp = make_inputs(seed, cell.config, S)
    jax.block_until_ready(inp.X)
    timing["inputs_s"] = time.perf_counter() - t_start
    prog = build_program(cell, inp, seed, fault)
    del inp
    losses, m1, theta3 = setup_rounds(prog, cell.config["opt"] == "adam")
    timing["setup_rounds_s"] = time.perf_counter() - t_start
    compile_s = log.seconds

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    span = jax.profiler.TraceAnnotation if traced else None
    try:
        if traced:
            jax.profiler.start_trace(tdir)
        setup_s = time.perf_counter() - t_start
        win = drive_window(prog, seconds, w, log, span)
        if traced:
            jax.profiler.stop_trace()
        device = device_info(cell.chips)
        del prog
        gc.collect()
        peaks = load_json(BENCH, "peaks.json")["devices"]
        ctx = Context(cell=cell, window=win, compile_s=compile_s, seeds=S,
                      peaks=peaks.get(device["kind"]) or {})
        if traced:
            ctx.events = trace.load(tdir, cell.chips)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    prog_out = {"losses": losses, "m1": m1, "theta3": theta3}
    t_ref = time.perf_counter()
    checks = compare.judge(reference_readings(cell, seed, prog_out),
                           cell.limits)
    timing["reference_s"] = time.perf_counter() - t_ref
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    out = {"correct": bool(correct), "attempted": win.windows,
           "failed": win.failed}
    if traced:
        if device["kind"] not in peaks:
            raise KeyError(f"device kind {device['kind']!r} is not in "
                           f"bench/peaks.json")
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        chips = [str(c) for c in range(cell.chips)]
        device["busy_s"] = sum(trace.busy_ns(ctx.events, c)
                               for c in chips) * 1e-9 / cell.chips
        lo, hi = trace.window(ctx.events)
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {"device_ops": trace.top_ops(ctx.events),
                            "idle_gaps": trace.idle_gaps(ctx.events, "0")}
    else:
        users = cell.config["C"] * cell.config["M"]
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] == "user_rounds_per_s":
                v = S * users * win.rounds / win.seconds
            elif m["name"] == "setup_s":
                v = setup_s
            else:
                raise KeyError(f"no harness rule for end-to-end metric "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out.update(metrics=metrics, device=device,
               window={"seconds": win.seconds, "rounds": win.rounds,
                       "compiles": win.compiles,
                       "cache_hits": log.cache_hits,
                       "compile_s": compile_s},
               timing=timing, checks=checks)
    return out
