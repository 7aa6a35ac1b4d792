"""The reduction from a profiler trace to device numbers, on a small
excerpt recorded from a TPU v5e run of `fig2_iid.fused_map` (the
events `bench.trace.load` reads from the `.xplane.pb`, kept as JSON)."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace  # noqa: E402
from bench.harness import metric_reader  # noqa: E402

KERNEL = metric_reader("hop_kernel_ms").__globals__["KERNEL"]

with open(os.path.join(HERE, "data", "fig2_fused_trace_excerpt.json")) as f:
    EVENTS = json.load(f)


def busy_mask(events, chip):
    """Independent reckoning: one boolean per nanosecond of the window."""
    lo, hi = trace.window(events)
    mask = np.zeros(int(hi - lo), bool)
    for _, s, d in events["ops"][chip]:
        a, b = max(int(s - lo), 0), min(int(s + d - lo), len(mask))
        if b > a:
            mask[a:b] = True
    return mask


def test_busy_union_matches_a_per_nanosecond_mask():
    mask = busy_mask(EVENTS, "0")
    assert trace.busy_ns(EVENTS, "0") == pytest.approx(mask.sum(), abs=2)
    assert 0 < mask.sum() < mask.size


def test_idle_gaps_are_the_longest_unbusy_runs():
    mask = busy_mask(EVENTS, "0")
    edges = np.flatnonzero(np.diff(np.concatenate(
        [[1], mask.astype(np.int8), [1]])))
    runs = sorted(((edges[1::2] - edges[::2]) * 1e-9).tolist(),
                  reverse=True)
    gaps = trace.idle_gaps(EVENTS, "0", n=5)
    assert [g[1] for g in gaps] == pytest.approx(runs[:5], abs=3e-9)
    assert {g[0] for g in gaps} <= {"dispatch", "fetch", "other"}


def test_kernel_time_sums_the_named_kernel():
    ns = trace.kernel_ns(EVENTS, KERNEL)
    lo, hi = trace.window(EVENTS)
    want = sum(min(s + d, hi) - max(s, lo)
               for ops in EVENTS["ops"].values() for n, s, d in ops
               if n.split(".")[0] == "fused_mac" and s < hi and s + d > lo)
    assert ns == want > 0
    assert trace.kernel_ns(EVENTS, r"no_such_kernel") == 0


def test_top_ops_are_sorted_and_bounded_by_the_window():
    lo, hi = trace.window(EVENTS)
    top = trace.top_ops(EVENTS)
    secs = [t for _, t in top]
    assert secs == sorted(secs, reverse=True) and len(top) <= 10
    assert sum(secs) >= trace.busy_ns(EVENTS, "0") * 1e-9 * 0.999 or \
        len(top) == 10
    assert max(secs) <= (hi - lo) * 1e-9


def test_merged_intervals():
    assert trace.merged([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [
        [1, 4], [5, 10]]
