"""Structured JSONL run journal for the sweep engine.

One line per event, schema ``repro.obs.trace/v1``.  Every record
carries ``event`` (one of `EVENTS`) and ``t`` (seconds since the
writer opened, from `time.perf_counter` — monotonic, so deltas are
trustworthy), plus event-specific fields:

- ``run_start`` — schema tag, jax version/backend, device count, UTC
  timestamp.  Always the first line.
- ``scenario_start`` — scenario name, seed count, round count, driver,
  engine metadata.
- ``compile`` — the round function was (re)traced since the last
  event: total ``n_traces`` (the sweep engine's trace counter) and how
  many were new.
- ``window`` — one eval window driven: final ``round``, ``rounds`` in
  the window, wall ``seconds``.  Stepwise windows time dispatch +
  execution + metric fetch; chunked windows carry
  ``enqueue_only: true`` — the chunked driver is asynchronous by
  design (one device sync per scenario), so the per-window number is
  the host's time in the chunk call, not execution time.  Where the
  runtime's queue of executions is full, that call waits for room, so
  the number includes the wait; a ``--profile`` trace puts each
  call's ``sweep.dispatch`` span beside the device's ops, which shows
  how much of it was that wait.
- ``telemetry`` — per-eval-window scalar summary of the in-program
  telemetry block (`repro.obs.telemetry.summarize`), emitted when the
  scenario ran with ``telemetry=True``.
- ``checkpoint`` — one sweep-carry save (`repro.ft.ckpt`): round
  cursor, path, wall seconds, attempts.
- ``guard`` — the non-finite guard (`repro.ft.guard`) tripped:
  scenario, round, cumulative trips, policy.
- ``fault`` — an injected or recovered fault (`repro.ft.faults`):
  checkpoint-save IO retries, imminent injected crashes.
- ``scenario_end`` — totals: wall seconds, drive seconds, dispatches,
  traces, final mean accuracy.
- ``run_end`` — always the last line (written by `TraceWriter.close`).

Usage (the sweep CLI wires ``--trace``):

    PYTHONPATH=src python -m repro.sim.sweep --scenarios fig2_iid \
        --quick --telemetry --trace results/run.jsonl
    PYTHONPATH=src python -m repro.obs.trace results/run.jsonl

The second command validates a journal against the schema (exit 1 on
any violation) and prints event counts — the CI trace-smoke gate.
``--allow-truncated-tail`` tolerates exactly the damage a killed run
leaves (a torn final line, a missing ``run_end``, an unclosed
scenario) for post-crash audits; every line before the tail must still
validate — each line is flushed AND fsynced before the writer returns,
so everything `emit` completed survives a SIGKILL.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = "repro.obs.trace/v1"

EVENTS = ("run_start", "scenario_start", "compile", "window",
          "telemetry", "checkpoint", "guard", "fault", "scenario_end",
          "run_end")


class TraceWriter:
    """Append-only JSONL event writer (flushed + fsynced per event, so
    even a SIGKILLed run leaves a valid, replayable journal up to its
    last completed `emit` — it just misses ``run_end``, which the
    validator reports unless told ``allow_truncated_tail``)."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "w")
        self._t0 = time.perf_counter()
        self._closed = False
        import jax  # deferred: the validator CLI must not pay this

        self.emit("run_start", schema=SCHEMA_VERSION,
                  jax_version=jax.__version__,
                  backend=jax.default_backend(),
                  device_count=jax.device_count(),
                  timestamp=datetime.datetime.now(
                      datetime.timezone.utc).isoformat(timespec="seconds"))

    def emit(self, event: str, **fields) -> None:
        if event not in EVENTS:
            raise ValueError(f"unknown trace event {event!r}; known: "
                             f"{', '.join(EVENTS)}")
        if self._closed:
            raise ValueError(f"trace {self.path!r} is closed")
        rec = {"event": event,
               "t": round(time.perf_counter() - self._t0, 6), **fields}
        self._f.write(json.dumps(rec) + "\n")
        # crash consistency: the line must be durable before control
        # returns — a later hard kill (SIGKILL / os._exit) must not be
        # able to lose it, or the post-crash audit lies
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        if self._closed:
            return
        self.emit("run_end")
        self._closed = True
        self._f.close()

    def __enter__(self) -> "TraceWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def validate_trace(path: str, allow_truncated_tail: bool = False
                   ) -> Tuple[Dict[str, int], List[str]]:
    """Check a journal against the v1 schema.  Returns ``(event
    counts, errors)``; an empty error list means the file is valid.

    ``allow_truncated_tail`` tolerates the exact damage a killed run
    leaves — an invalid FINAL line (torn mid-write), a missing
    ``run_end``, and scenarios started but never ended.  Anything else
    (torn interior lines, unknown events, a bad schema header) still
    errors: per-line fsync guarantees the body is intact.
    """
    errors: List[str] = []
    events: List[Dict] = []
    lines: List[Tuple[int, str]] = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if line:
                lines.append((i, line))
    for n, (i, line) in enumerate(lines):
        is_tail = n == len(lines) - 1
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            if not (allow_truncated_tail and is_tail):
                errors.append(f"line {i}: not valid JSON ({e.msg})")
            continue
        if not isinstance(rec, dict):
            errors.append(f"line {i}: not a JSON object")
            continue
        ev = rec.get("event")
        if ev not in EVENTS:
            errors.append(f"line {i}: unknown event {ev!r}")
        if not isinstance(rec.get("t"), (int, float)):
            errors.append(f"line {i}: missing/non-numeric 't'")
        events.append(rec)
    if not events:
        errors.append("empty trace (no events)")
        return {}, errors
    first = events[0]
    if first.get("event") != "run_start":
        errors.append(f"first event is {first.get('event')!r}, "
                      f"expected 'run_start'")
    elif first.get("schema") != SCHEMA_VERSION:
        errors.append(f"schema {first.get('schema')!r} != "
                      f"{SCHEMA_VERSION!r}")
    if events[-1].get("event") != "run_end" and not allow_truncated_tail:
        errors.append(f"last event is {events[-1].get('event')!r}, "
                      f"expected 'run_end' (truncated run?)")
    starts = [e.get("scenario") for e in events
              if e.get("event") == "scenario_start"]
    ends = [e.get("scenario") for e in events
            if e.get("event") == "scenario_end"]
    if (sorted(map(str, starts)) != sorted(map(str, ends))
            and not allow_truncated_tail):
        errors.append(f"unbalanced scenario_start/scenario_end: "
                      f"{starts} vs {ends}")
    for i, e in enumerate(events, 1):
        if e.get("event") == "window":
            for k in ("round", "rounds", "seconds"):
                if not isinstance(e.get(k), (int, float)):
                    errors.append(
                        f"event {i}: window missing numeric {k!r}")
    counts: Dict[str, int] = {}
    for e in events:
        ev = e.get("event")
        counts[ev] = counts.get(ev, 0) + 1
    return counts, errors


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Validate a repro.obs.trace JSONL run journal")
    ap.add_argument("trace", help="journal file written via --trace")
    ap.add_argument("--allow-truncated-tail", action="store_true",
                    help="post-crash audit mode: tolerate a torn final "
                         "line, a missing run_end and unclosed "
                         "scenarios (exactly the damage a killed run "
                         "leaves); everything else must still validate")
    args = ap.parse_args(argv)
    counts, errors = validate_trace(
        args.trace, allow_truncated_tail=args.allow_truncated_tail)
    if args.allow_truncated_tail:
        _, strict = validate_trace(args.trace)
        for e in strict:
            if e not in errors:
                print(" ~ tolerated:", e)
    for ev in EVENTS:
        if counts.get(ev):
            print(f"  {ev:16s} {counts[ev]}")
    if errors:
        print(f"INVALID ({len(errors)} schema violations):")
        for e in errors:
            print(" -", e)
        return 1
    print(f"valid {SCHEMA_VERSION} journal "
          f"({sum(counts.values())} events)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
