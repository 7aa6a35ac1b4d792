"""Device time of the W-HFL round by the phases the program names.

The round program names its phases with `jax.named_scope`
(`repro.core.whfl.SCOPES`): ``whfl.train`` with ``whfl.batch`` nested
in it, ``whfl.cluster_hop``, ``whfl.ps_hop``, ``whfl.update`` and
``whfl.eval``.  The names reach each HLO instruction's ``op_name``
metadata.  A TPU trace names a device op after its instruction
("fusion.211", "fused_mac.25"), so an op's scope is read from the HLO
text of the compiled chunk program: the innermost ``whfl.*`` scope in
the instruction's ``op_name``, for a fusion that of its fused
computation's root; an op with none is ``other``.

`op_scopes` parses HLO text, `scope_ns` splits a window's device time
by scope (self time, as `bench.trace.top_ops` counts it, so the scopes
and ``other`` sum to the busy time), and `run_scopes` compiles the
cell's chunk program anew after the window, in traced runs only, to
read its HLO.  A program that names no phase (one older than the
scopes) maps every op to ``other``, and `per_round_ms` then reads
nothing.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import trace

SCOPES = ("whfl.train", "whfl.batch", "whfl.cluster_hop", "whfl.ps_hop",
          "whfl.update", "whfl.eval")
OTHER = "other"

SCOPE = re.compile("(?:" + "|".join(map(re.escape, SCOPES)) + r")(?![\w.])")
INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost whfl.* scope of an ``op_name``, else ``other``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else OTHER


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: scope} over every computation of an HLO
    module's text; a fusion takes the scope of its fused computation's
    root, or its own where the root names none."""
    own, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = INSTR.match(line)
        if not m:
            continue
        name = m.group(2)
        op = OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else OTHER
        c = CALLS.search(line)
        if c and " fusion(" in line:
            calls[name] = c.group(1)
        if m.group(1):
            roots[comp] = name
    out = dict(own)
    for name, comp in calls.items():
        root = own.get(roots.get(comp), OTHER)
        if root != OTHER:
            out[name] = root
    return out


def scope_ns(events: dict, scopes: dict) -> dict:
    """{scope: ns}: each scope's self time in the window, averaged over
    the chips; ops the map lacks count as ``other``."""
    lo, hi = trace.window(events)
    tot = defaultdict(float)
    for ops in events["ops"].values():
        for name, t in trace.self_times(ops, lo, hi):
            tot[scopes.get(name, OTHER)] += t
    k = max(len(events["ops"]), 1)
    return {s: t / k for s, t in tot.items()}


def chunk_hlo(cell, seed: int = 0) -> str:
    """HLO text of `cell`'s compiled chunk program, built as the
    harness builds it.  Weights and keys are arguments, and data and
    geometry follow the config's `data_seed`, so any seed gives the
    program the window ran (from the compile cache where it is on)."""
    from bench.harness import build_program
    from bench.inputs import make_inputs

    inp = make_inputs(seed, cell.config, cell.traffic["seeds_per_dispatch"])
    prog = build_program(cell, inp, seed)
    P, P_is = prog.feed(cell.traffic["rounds_per_window"])
    chunk = prog.chunk       # functools.partial(jitted, X, Y)
    return chunk.func.lower(*chunk.args, prog.state, prog.keys, P,
                            P_is).compile().as_text()


def run_scopes(ctx) -> dict:
    """`op_scopes` of the run's chunk program, compiled once a run and
    kept with the run's reader context."""
    if getattr(ctx, "scopes", None) is None:
        ctx.scopes = op_scopes(chunk_hlo(ctx.cell))
    return ctx.scopes


def per_round_ms(ctx, *names) -> float | None:
    """Device milliseconds per round in the scopes `names` (self time,
    averaged over the chips); None without a trace or where the program
    names no phase."""
    if not ctx.events or ctx.window.rounds == 0:
        return None
    scopes = run_scopes(ctx)
    if all(s == OTHER for s in scopes.values()):
        return None
    ns = scope_ns(ctx.events, scopes)
    return 1e-6 * sum(ns.get(n, 0.0) for n in names) / ctx.window.rounds
