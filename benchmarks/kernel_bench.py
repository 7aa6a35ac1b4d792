"""Microbenchmark + parity gates for the OTA receive combine backends.

Covers the three compute cores behind `repro.core.channel`:

- ``oracle``      — pure-jnp einsum fold (ground truth),
- ``slab_kernel`` — blocked Pallas combine over a materialized
  [U, K, N] channel slab (interpret on CPU — correctness/latency proxy
  only; the compiled path targets TPU),
- ``fused``       — Pallas combine that derives the channels in-kernel
  from a counter PRNG; channel memory O(block) instead of O(U*K*N).

Emits the benchmark-suite CSV convention on stdout and, with ``--out``,
a structured JSON document (``BENCH_kernel.json``) so CI can accumulate
the perf trajectory: per-record wall time, effective GFLOP/s and the
analytic channel-memory footprint, plus the parity-gate results.

``--smoke`` is the CI gate: tiny shapes, plus (a) slab kernel vs oracle
and (b) fused kernel vs its materialized reference at <= 1e-4 relative
error, both in interpret mode.  ``--scale`` runs the no-slab
demonstration hop (U=4096, K=32, N=8192 — the [U,K,N] slab would be
8 GiB; the fused path never builds it).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (fused_combine, fused_mac_ref, interpret_mode,
                           mf_combine)

SCHEMA_VERSION = "repro.bench.kernel/v1"

FUSED_BLOCK = dict(block_n=512, block_k=8, block_u=32)
_SEED = np.asarray([0xBEEF, 7], np.uint32)


def _bench(f, *args, n: int = 3) -> float:
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / n


def _make_inputs(rng, U: int, K: int, N: int):
    cx = lambda *shape: jnp.asarray(
        (rng.standard_normal(shape)
         + 1j * rng.standard_normal(shape)).astype(np.complex64))
    return cx(U, K, N), cx(U, N), cx(K, N)


def _slab_bytes(U: int, K: int, N: int) -> int:
    return U * K * N * 8          # complex64 channel slab


def _fused_bytes(U: int) -> int:
    bu, bk, bn = (FUSED_BLOCK["block_u"], FUSED_BLOCK["block_k"],
                  FUSED_BLOCK["block_n"])
    bu = min(bu, U)
    # per-step working set: one generated g block (planar re/im) + the
    # four [bk, bn] scratch accumulators
    return bu * bk * bn * 2 * 4 + 4 * bk * bn * 4


def _record(name: str, backend: str, U: int, K: int, N: int, dt: float,
            channel_bytes: int) -> Dict:
    return {
        "name": name, "backend": backend, "U": U, "K": K, "N": N,
        "us_per_call": 1e6 * dt,
        "gflops": 8.0 * U * K * N / dt / 1e9,  # ~8 flops/(u,k,n) cmac
        "channel_bytes": channel_bytes,
        # execution context, so the accumulated trajectory is comparable
        # across runners (CPU-interpret vs TPU-compiled, 1 vs N devices).
        # Only the Pallas cores are interpreted (on CPU); the jnp oracle
        # is XLA-compiled everywhere.
        "device_count": jax.device_count(),
        "jax_backend": jax.default_backend(),
        "exec_mode": ("interpret"
                      if backend != "oracle" and interpret_mode()
                      else "compiled"),
    }


def _bench_oracle(rng, U, K, N) -> Dict:
    h, t, z = _make_inputs(rng, U, K, N)
    f = jax.jit(lambda a, b, c: mf_combine(a, b, c, use_kernel=False))
    dt = _bench(f, h, t, z)
    return _record(f"ref_U{U}_K{K}_N{N}", "oracle", U, K, N, dt,
                   _slab_bytes(U, K, N))


def _bench_slab(rng, U, K, N) -> Dict:
    h, t, z = _make_inputs(rng, U, K, N)
    f = jax.jit(lambda a, b, c: mf_combine(a, b, c, use_kernel=True))
    dt = _bench(f, h, t, z)
    return _record(f"slab_U{U}_K{K}_N{N}", "slab_kernel", U, K, N, dt,
                   _slab_bytes(U, K, N))


def _bench_fused(rng, U, K, N) -> Dict:
    t = jnp.asarray((rng.standard_normal((U, N))
                     + 1j * rng.standard_normal((U, N))).astype(np.complex64))
    amp = jnp.ones((1, U), jnp.float32)
    w = jnp.ones((1, U), jnp.float32)
    seed = jnp.asarray(_SEED)
    f = jax.jit(lambda s, tt: fused_combine(s, tt, amp, w, K=K,
                                            sigma_h2=1.0, sigma_z2=1.0))
    dt = _bench(f, seed, t)
    return _record(f"fused_U{U}_K{K}_N{N}", "fused", U, K, N, dt,
                   _fused_bytes(U))


def _parity_gates() -> List[Dict]:
    """CI correctness gates, interpret mode on CPU."""
    gates = []
    # slab Pallas kernel vs the jnp oracle
    rng = np.random.default_rng(1)
    h, t, z = _make_inputs(rng, 4, 8, 512)
    y_k = mf_combine(h, t, z, use_kernel=True)
    y_r = mf_combine(h, t, z, use_kernel=False)
    rel = float(jnp.max(jnp.abs(y_k - y_r))) / float(jnp.max(jnp.abs(y_r)))
    gates.append({"name": "slab_vs_oracle", "max_rel_err": rel,
                  "tol": 1e-2, "ok": rel < 1e-2})
    # fused kernel vs its materialized counter-PRNG reference (the
    # acceptance gate: <= 1e-4 relative)
    for (B, U, K, N) in [(1, 4, 8, 512), (3, 5, 7, 130)]:
        rng = np.random.default_rng(U + N)
        t_re = jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
        t_im = jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
        amp = jnp.asarray(rng.uniform(0.5, 2.0, (B, U)), jnp.float32)
        w = jnp.asarray(rng.integers(0, 2, (B, U)), jnp.float32)
        seed = jnp.asarray(_SEED)
        kw = dict(K=K, sigma_h2=1.0, sigma_z2=2.0)
        y = fused_combine(seed, jax.lax.complex(t_re, t_im), amp, w, **kw)
        rr, ri = fused_mac_ref(seed, t_re, t_im, amp, w, **kw)
        ref = jax.lax.complex(rr, ri)
        rel = float(jnp.max(jnp.abs(y - ref))) / float(jnp.max(jnp.abs(ref)))
        gates.append({"name": f"fused_vs_ref_B{B}_U{U}_K{K}_N{N}",
                      "max_rel_err": rel, "tol": 1e-4, "ok": rel < 1e-4})
    return gates


def main(quick: bool = True, smoke: bool = False,
         scale: bool = False) -> Tuple[List[str], Dict]:
    records: List[Dict] = []
    parity: List[Dict] = []

    shapes = [(20, 100, 3925), (4, 100, 3925)]  # MNIST: C*M users, IS hop
    if smoke:
        shapes = [(4, 8, 512)]                  # CI: seconds, interpret-safe
    elif not quick:
        shapes.append((20, 100, 153749))        # CIFAR model size
    rng = np.random.default_rng(0)
    for (U, K, N) in shapes:
        records.append(_bench_oracle(rng, U, K, N))
        records.append(_bench_fused(rng, U, K, N))
        if smoke:
            records.append(_bench_slab(rng, U, K, N))

    if scale:
        # the no-slab hop: U=4096, K=32, N=8192 — only the fused
        # backend can run this without an 8 GiB channel tensor
        records.append(_bench_fused(np.random.default_rng(2), 4096, 32,
                                    8192))

    if smoke or scale:
        parity = _parity_gates()
        for g in parity:
            assert g["ok"], (g["name"], g["max_rel_err"], g["tol"])

    lines = []
    for r in records:
        lines.append(
            f"kernel/{r['name']},{r['us_per_call']:.1f},"
            f"gflops={r['gflops']:.2f};"
            f"channel_mb={r['channel_bytes'] / 1e6:.2f};"
            f"backend={r['backend']}")
    for g in parity:
        lines.append(f"kernel/parity_{g['name']},0.0,"
                     f"max_rel_err={g['max_rel_err']:.2e};ok={g['ok']}")

    doc = {"schema": SCHEMA_VERSION, "backend": jax.default_backend(),
           "device_count": jax.device_count(),
           "records": records, "parity": parity}
    return lines, doc


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: tiny shapes + slab-vs-oracle and "
                         "fused-vs-reference parity checks (interpret)")
    ap.add_argument("--scale", action="store_true",
                    help="run the U=4096, K=32, N=8192 fused hop "
                         "(no [U,K,N] slab is ever materialized)")
    ap.add_argument("--out", default=None,
                    help="write the JSON document here "
                         "(e.g. results/BENCH_kernel.json)")
    args = ap.parse_args()
    out_lines, out_doc = main(quick=not args.full, smoke=args.smoke,
                              scale=args.scale)
    for ln in out_lines:
        print(ln)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out_doc, f, indent=1)
        print("wrote", args.out)
