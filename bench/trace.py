"""Reduction of a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` the JAX profiler writes into plain
events: each chip's device operations and the harness's own host spans
(`bench.window`, `bench.dispatch`, `bench.fetch`), all on the
profiler's clock in nanoseconds.  Everything after that works on those
events, so it can be checked on a small recorded excerpt:

- busy time: the union of the intervals in which an operation ran on a
  chip, clipped to the traced window, averaged over the chips used;
- kernel time: the summed device durations of the operations whose
  name matches a pattern;
- idle gaps: the stretches of the window in which a chip ran nothing,
  each named by the host span its midpoint falls in (dispatch, fetch,
  or other).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(trace_dir: str, chips: int) -> dict:
    """{"ops": {chip: [(name, start_ns, dur_ns)]}, "spans": [(name,
    start_ns, dur_ns)]} from the newest xplane file under `trace_dir`,
    for the first `chips` TPU planes."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, spans = defaultdict(list), []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))].extend(
                        (op_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"ops": {str(k): v for k, v in sorted(ops.items())},
            "spans": spans}


def op_name(hlo: str) -> str:
    """"%fused_mac.25 = f32[4,2,4096]{...} custom-call(...)" ->
    "fused_mac.25": a TPU trace names each op by its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def window(events: dict):
    """(start_ns, end_ns) of the `bench.window` span."""
    wins = [(s, s + d) for n, s, d in events["spans"] if n == "bench.window"]
    if len(wins) != 1:
        raise ValueError(f"expected one bench.window span, found {len(wins)}")
    return wins[0]


def merged(intervals, lo, hi):
    """Union of (start, end) intervals clipped to [lo, hi], sorted and
    disjoint."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: dict, chip: str) -> float:
    lo, hi = window(events)
    return sum(e - s for s, e in merged(
        ((s, s + d) for _, s, d in events["ops"].get(chip, ())), lo, hi))


def kernel_ns(events: dict, pattern: str) -> float:
    """Summed device time, over every chip, of the operations whose
    name matches `pattern`, within the window."""
    lo, hi = window(events)
    rx = re.compile(pattern)
    return sum(min(s + d, hi) - max(s, lo)
               for ops in events["ops"].values() for n, s, d in ops
               if rx.search(n) and s < hi and s + d > lo)


def self_times(ops, lo, hi):
    """[(name, self ns)]: each op's time in [lo, hi] less that of the
    ops nested in it (a while loop holds its body's ops)."""
    out, stack = [], []      # stack: [name, end, self ns]
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    out.extend(tuple(x[::2]) for x in stack)
    return out


def top_ops(events: dict, n: int = 10):
    """[[name, seconds], ...]: the operations that took most device
    time (self time, nested ops taken out) in the window, averaged over
    the chips."""
    lo, hi = window(events)
    tot = defaultdict(float)
    for ops in events["ops"].values():
        for name, t in self_times(ops, lo, hi):
            tot[name] += t * 1e-9
    k = max(len(events["ops"]), 1)
    return [[name, t / k] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: dict, chip: str, n: int = 10):
    """[[host span, seconds], ...]: the longest stretches of the window
    in which `chip` ran nothing, each named by the harness's host span
    ("dispatch", "fetch") that covers its midpoint, else "other"."""
    lo, hi = window(events)
    busy = merged(((s, s + d) for _, s, d in events["ops"].get(chip, ())),
                  lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = [(s, s + d, name[len(SPAN_PREFIX):])
             for name, s, d in events["spans"] if name != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        what = next((nm for a, b, nm in spans if a <= mid <= b), "other")
        out.append([what, (e - s) * 1e-9])
    return out

