"""Device time of the model's own layers, by the scopes the model code
names (``cnn.conv``: a convolution of `repro.models.paper_models` with
its bias add, and their backward ops).

The round's phases (`bench/scopes.py`) and the model's layers are read
from the same HLO text and split the window's device time the same way
(`scopes.scope_ns`), each by its own names: an instruction's model
scope is the innermost ``cnn.*`` scope of its ``op_name``, for a fusion
that of its fused computation's root, else ``other``.  The ops of a
model scope still count in their round phase (``whfl.train`` or
``whfl.eval``).  A program that names no model scope (one older than
the names) maps every op to ``other``, and `per_round_ms` then reads
nothing.
"""
from __future__ import annotations

import re

from bench import scopes

SCOPE = re.compile(r"(?<![\w.])cnn\.[A-Za-z_]\w*(?![\w.])")
OTHER = scopes.OTHER


def scope_of(op_name: str) -> str:
    """The innermost cnn.* scope of an ``op_name``, else ``other``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else OTHER


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: model scope} over every computation of an HLO
    module's text; a fusion takes the scope of its fused computation's
    root, or its own where the root names none."""
    own, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = scopes.INSTR.match(line)
        if not m:
            continue
        name = m.group(2)
        op = scopes.OP_NAME.search(line)
        own[name] = scope_of(op.group(1)) if op else OTHER
        c = scopes.CALLS.search(line)
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


def run_scopes(ctx) -> dict:
    """`op_scopes` of the run's chunk program, compiled once a run (a
    load from the compile cache where it is on) and kept with the
    run's reader context."""
    if getattr(ctx, "model_scopes", None) is None:
        ctx.model_scopes = op_scopes(scopes.chunk_hlo(ctx.cell))
    return ctx.model_scopes


def per_round_ms(ctx, *names) -> float | None:
    """Device milliseconds per round in the model scopes `names` (self
    time averaged over the chips, as `scopes.scope_ns` counts it);
    None without a trace or where the program names no model scope."""
    if not ctx.events or ctx.window.rounds == 0:
        return None
    ops_scope = run_scopes(ctx)
    if all(s == OTHER for s in ops_scope.values()):
        return None
    ns = scopes.scope_ns(ctx.events, ops_scope)
    return 1e-6 * sum(ns.get(n, 0.0) for n in names) / ctx.window.rounds
