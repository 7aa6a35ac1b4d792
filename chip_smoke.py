"""Smoke run of the W-HFL sweep engine on a TPU, at the paper's widths.

    python chip_smoke.py                # one chip: the four phases below
    python chip_smoke.py --four-chips   # 2x2 mesh: scale_u16384 sharded
                                        # (both combines) vs a 1x1 mesh

One-chip phases, each driven through `repro.sim.sweep.SweepRunner` with
models initialised at random from seeds and synthetic data:

  fig2_iid        paper size (C=4, M=5, K=K_ps=100, batch 500, the
                  7,850-parameter MLP), equivalent channel, 5 rounds,
                  stepwise and chunked drivers
  fig2_iid_fused  the same, with the faithful OTA hops through the fused
                  Pallas kernel (M=5, K=100)
  scale_u1024     C=8, M=128, K=16, fused kernel, 2 rounds
  fig3_cifar      the 308,394-parameter CNN, tau=5, batch 128, 2 rounds

Each phase prints one JSON line with its scenario, metrics, seconds and
the seconds spent compiling.  Every phase checks that its metrics are
finite; fig2_iid checks that its loss fell.  The fused phases also check
that the round program holds the compiled kernel (``tpu_custom_call``)
and that the kernel agrees with `fused_mac_ref` at each OTA hop's shape
to 1e-4 of max|y|.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any
failure raises and exits non-zero, and without a TPU the script exits
non-zero before the first phase.  Compiled programs are kept in JAX's
persistent cache (`repro.sim.compile_cache`).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp

from repro.core.aggregation import make_flat_spec
from repro.exec import ShardedSweepRunner
from repro.kernels import (canonical_block_u, fused_mac,
                           fused_mac_ref, interpret_mode)
from repro.nn.core import split_params
from repro.obs.diff import diff_trees
from repro.sim.compile_cache import enable_compile_cache
from repro.sim.scenario import Scenario, get_scenario
from repro.sim.sweep import SweepRunner, state_doc, sweep_to_json

PARITY_TOL = 1e-4      # kernel vs reference, relative to max|y|
REF_TILE_N = 512       # symbols per reference call (bounds its memory)
METRICS = ("acc", "loss", "edge_power", "is_power")


class CompileLog:
    """Backend compile seconds and persistent-cache hits, read from
    JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _lower(fn, args) -> str:
    """StableHLO text of the jitted `fn` (or a `functools.partial` of
    one, as the single engine binds its data shards) at `args`."""
    if isinstance(fn, functools.partial):
        return _lower(fn.func, fn.args + args)
    return fn.lower(*args).as_text()


def probed(runner_cls):
    """`runner_cls` whose round and chunk programs keep the StableHLO
    text of their first lowering in ``self.programs``."""

    class Probed(runner_cls):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.programs = []

        def _probe(self, fn):
            lowered = False

            def call(*args):
                nonlocal lowered
                if not lowered:
                    lowered = True
                    self.programs.append(_lower(fn, args))
                return fn(*args)
            return call

        def _build_round(self, *args, **kw):
            return self._probe(super()._build_round(*args, **kw))

        def _build_chunk(self, *args, **kw):
            return self._probe(super()._build_chunk(*args, **kw))

    return Probed


def n_symbols(sc: Scenario) -> int:
    """N: complex OTA symbols per model update (half the flat size)."""
    init_fn = sc.task_fns()[0]
    params = split_params(init_fn(jax.random.PRNGKey(0)))[0]
    return make_flat_spec(params).two_n // 2


def ota_hops(sc: Scenario):
    """(name, B, U, K, block_u) of the scenario's two fused OTA hops:
    the cluster hop (every IS hears all C*M users) and the IS -> PS
    hop (the PS hears the C ISs)."""
    return [("cluster", sc.C, sc.C * sc.M, sc.K, canonical_block_u(sc.M)),
            ("is_ps", 1, sc.C, sc.K_ps, 32)]


def kernel_error(B, U, K, N, *, sigma_h2, sigma_z2, block_u, seed=0):
    """max |fused_mac - fused_mac_ref| / max |ref| on random inputs at
    (B, U, K, N).  The reference materializes the channels, so it runs
    in symbol tiles at the tile's counter base (`n_base`), which draws
    exactly the full call's channels there."""
    rng = np.random.default_rng(seed)
    t_re, t_im = (jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
                  for _ in range(2))
    amp = jnp.asarray(rng.uniform(0.5, 2.0, (B, U)), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (B, U)), jnp.float32)
    words = jnp.asarray([seed, 0x5EED], jnp.uint32)
    kw = dict(K=K, sigma_h2=sigma_h2, sigma_z2=sigma_z2)
    y_re, y_im = fused_mac(words, t_re, t_im, amp, w, block_u=block_u,
                           interpret=interpret_mode(), **kw)
    ref = jax.jit(fused_mac_ref, static_argnames=tuple(kw))
    tiles = [ref(words, t_re[:, n0:n0 + REF_TILE_N],
                 t_im[:, n0:n0 + REF_TILE_N], amp, w, n_base=n0, **kw)
             for n0 in range(0, N, REF_TILE_N)]
    r_re = jnp.concatenate([t[0] for t in tiles], axis=-1)
    r_im = jnp.concatenate([t[1] for t in tiles], axis=-1)
    scale = float(jnp.abs(jax.lax.complex(r_re, r_im)).max())
    err = max(float(jnp.abs(y_re - r_re).max()),
              float(jnp.abs(y_im - r_im).max()))
    return err / scale


def check_metrics(res, label: str) -> None:
    for key in METRICS:
        vals = np.asarray(getattr(res, key), np.float64)
        if vals.size == 0 or not np.all(np.isfinite(vals)):
            raise AssertionError(f"{label}: non-finite {key}: {vals}")


def run_phase(label: str, sc: Scenario, log: CompileLog, *,
              drivers=("stepwise",), fused: bool = False,
              loss_falls: bool = False) -> dict:
    """Run `sc` once per driver through `SweepRunner`, check it, and
    print the phase's JSON line."""
    c0, h0 = log.seconds, log.cache_hits
    t0 = time.perf_counter()
    line = {"phase": label, "scenario": sc.name, "C": sc.C, "M": sc.M,
            "K": sc.K, "ota": f"{sc.ota_mode}/{sc.ota_backend or '-'}",
            "rounds": sc.rounds, "runs": []}
    for driver in drivers:
        runner = probed(SweepRunner)([sc], seeds=1, driver=driver)
        res = runner.run()[0]     # the drivers block on the final state
        check_metrics(res, f"{label}/{driver}")
        if loss_falls and not res.loss[0][-1] < res.loss[0][0]:
            raise AssertionError(
                f"{label}/{driver}: loss did not fall: {res.loss[0]}")
        if fused and not (runner.programs and all(
                "tpu_custom_call" in p for p in runner.programs)):
            raise AssertionError(
                f"{label}/{driver}: a round program has no compiled "
                f"kernel (tpu_custom_call)")
        line["runs"].append({
            "driver": driver,
            **{k: getattr(res, k)[0] for k in METRICS},
            "seconds": res.seconds,
            "drive_seconds": res.exec_info["drive_seconds"]})
    if fused:
        N = n_symbols(sc)
        topo = sc.make_topology()
        line["kernel_vs_ref"] = {}
        for hop, B, U, K, bu in ota_hops(sc):
            err = kernel_error(B, U, K, N, sigma_h2=topo.sigma_h2,
                               sigma_z2=topo.sigma_z2, block_u=bu)
            line["kernel_vs_ref"][hop] = {"B": B, "U": U, "K": K, "N": N,
                                          "rel_err": err}
            if not err <= PARITY_TOL:
                raise AssertionError(
                    f"{label}: kernel vs reference at the {hop} hop "
                    f"(B={B}, U={U}, K={K}, N={N}): {err:.3g} > "
                    f"{PARITY_TOL}")
    line["seconds"] = time.perf_counter() - t0
    line["compile_seconds"] = log.seconds - c0
    line["cache_hits"] = log.cache_hits - h0
    print(json.dumps(line), flush=True)
    return line


def one_chip(log: CompileLog) -> None:
    fig2 = get_scenario("fig2_iid").replace(total_IT=5, eval_every=1)
    run_phase("fig2_iid", fig2, log, drivers=("stepwise", "chunked"),
              loss_falls=True)
    run_phase("fig2_iid_fused",
              fig2.replace(total_IT=2, ota_mode="faithful",
                           ota_backend="fused"), log, fused=True)
    run_phase("scale_u1024", get_scenario("scale_u1024"), log, fused=True)
    run_phase("fig3_cifar",
              get_scenario("fig3_cifar").replace(total_IT=2, eval_every=1),
              log)


def device_bytes(n: int) -> list:
    """Peak and current bytes in use on each of the first n devices."""
    out = []
    for d in jax.devices()[:n]:
        st = d.memory_stats() or {}
        out.append({"device": d.id, "peak": st.get("peak_bytes_in_use"),
                    "in_use": st.get("bytes_in_use")})
    return out


def four_chips(log: CompileLog) -> None:
    """scale_u16384 (C=16, M=1024, K=4), one round, on a 2x2 mesh with
    both fused combines, against the same scenario on a 1x1 mesh."""
    sc = get_scenario("scale_u16384")

    def run(mesh, combine):
        c0, t0 = log.seconds, time.perf_counter()
        runner = ShardedSweepRunner([sc], seeds=1, mesh=mesh,
                                    combine=combine, keep_state=True)
        res = runner.run()[0]
        check_metrics(res, f"{sc.name}/{mesh}/{combine}")
        line = {"phase": "scale_u16384", "mesh": mesh, "combine": combine,
                **{k: getattr(res, k)[0] for k in METRICS},
                "peak_symbol_bytes": res.exec_info.get("peak_symbol_bytes"),
                "seconds": time.perf_counter() - t0,
                "compile_seconds": log.seconds - c0,
                "device_bytes": device_bytes(4)}
        print(json.dumps(line), flush=True)
        return {"metrics": sweep_to_json([res]), "state": state_doc([res])}

    # the 2x2 runs come first, so the per-device peaks they print are
    # not the 1x1 run's
    runs = {c: run("2x2", c) for c in ("gathered", "u_sharded")}
    ref = run("1x1", "gathered")
    for combine, docs in runs.items():
        dist = {}
        for part in ("metrics", "state"):
            res = diff_trees(ref[part], docs[part])
            if res.errors:
                raise AssertionError(f"2x2 {combine} vs 1x1 {part}: "
                                     f"{res.errors[:5]}")
            dist[part] = res.max_ulp
        fin = docs["metrics"]["scenarios"][0]["final"]
        fin_ref = ref["metrics"]["scenarios"][0]["final"]
        for k in ("acc_mean", "loss_mean"):
            if not np.isclose(fin[k], fin_ref[k], rtol=1e-3, atol=1e-6):
                raise AssertionError(f"2x2 {combine} vs 1x1: {k} "
                                     f"{fin[k]} != {fin_ref[k]}")
        print(json.dumps({"phase": "scale_u16384_parity",
                          "compare": f"2x2/{combine} vs 1x1/gathered",
                          "max_ulp": dist}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh scale_u16384 comparison "
                         "(needs four chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r}")
    if args.four_chips and len(devices) < 4:
        sys.exit(f"chip_smoke: --four-chips needs 4 chips, found "
                 f"{len(devices)}")
    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache,
                      "jax": jax.__version__}), flush=True)
    log = CompileLog()
    (four_chips if args.four_chips else one_chip)(log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
