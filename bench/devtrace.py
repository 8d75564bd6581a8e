"""Reduction of a profiler trace (`jax.profiler.ProfileData`) to numbers.

The traced window is the host annotation `bench.traced` that the harness
opens around the traced steps. Within it, per device plane
(`/device:TPU:<n>`):

- busy: the union of the intervals of the `XLA Ops` events (of `XLA
  Modules` where a plane has no op line);
- module time: the summed duration of each `XLA Modules` event, by its
  name without the trailing `(<id>)`, e.g. `jit_chunk_step`;
- op time: the summed duration of each op name;
- idle gaps: the stretches of the window not covered by busy, each
  attributed to the innermost host event (on `/host:CPU`) that covers the
  gap's midpoint, i.e. what the host was doing while the device waited.

Module time is kept per `bench.step` (the harness's span around each
`Scheduler.step()`), so readers can pair it with that step's work. Busy,
module, op and idle times are averaged over the device planes.
"""
from __future__ import annotations

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.traced"
STEP = "bench.step"
DROPPED = "Trace Buffers Dropped"


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def module_name(name: str) -> str:
    return name.split("(")[0].strip()


def host_events(pd) -> list:
    """(start_ns, end_ns, name) of every event on the host thread that
    opened the traced window, sorted by start."""
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
                if any(n == WINDOW for _, _, n in evs):
                    return sorted(evs)
    return []


def window_of(hosts) -> tuple:
    spans = [(s, e) for s, e, n in hosts if n == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def innermost(hosts, times) -> list:
    """For each time (ascending), the name of the shortest host event other
    than the window that covers it, by one sweep over `hosts` (sorted)."""
    out, active, i = [], [], 0
    for t in times:
        while i < len(hosts) and hosts[i][0] <= t:
            if hosts[i][2] != WINDOW:
                active.append(hosts[i])
            i += 1
        active = [h for h in active if h[1] >= t]
        best = min(active, key=lambda h: h[1] - h[0], default=None)
        out.append(best[2] if best else "no host event")
    return out


def _overlaps(s, e, spans) -> bool:
    return any(s < b and a < e for a, b in spans)


def _minus(lo, hi, holes) -> list:
    """[lo, hi] without the (merged, sorted) `holes`."""
    out, cur = [], lo
    for a, b in holes:
        if b <= cur or a >= hi:
            continue
        if a > cur:
            out.append([cur, a])
        cur = max(cur, b)
    if cur < hi:
        out.append([cur, hi])
    return out


def reduce(pd) -> dict:
    """Busy, module, op and idle times within the traced window. Where the
    profiler dropped device events (a `Trace Buffers Dropped` event, when a
    trace outgrows its serialization limit), those stretches are left out
    of the window, and so is every `bench.step` that overlaps one: `steps`
    lists each step with `covered` and its module times."""
    hosts = host_events(pd)
    lo, hi = window_of(hosts)
    planes = [p for p in pd.planes if DEVICE_PLANE.match(p.name)]
    dropped = union(
        _clip(ev.start_ns, ev.end_ns, lo, hi)
        for p in planes for line in p.lines for ev in line.events
        if ev.name == DROPPED
    )
    dropped = [d for d in dropped if d[1] > d[0]]
    covered = _minus(lo, hi, dropped)
    steps = [{"start": s, "end": e, "covered": not _overlaps(s, e, dropped),
              "module_ns": collections.Counter()}
             for s, e, n in hosts if n == STEP and lo <= s and e <= hi]
    starts = [st["start"] for st in steps]
    busy = 0.0
    ops: dict = collections.Counter()
    idle: dict = collections.Counter()
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        spans = []
        for ev in (op_line.events if op_line is not None else ()):
            s, e = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if e > s and not _overlaps(s, e, dropped):
                spans.append((s, e))
                ops[op_name(ev.name)] += e - s
        merged = union(spans)
        busy += sum(e - s for s, e in merged)
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                mid = (ev.start_ns + ev.end_ns) / 2
                i = bisect.bisect_right(starts, mid) - 1
                if i >= 0 and mid <= steps[i]["end"]:
                    steps[i]["module_ns"][module_name(ev.name)] += ev.duration_ns
        gaps = []
        for a, b in covered:
            edges = [a] + [x for s, e in merged if a <= s and e <= b
                           for x in (s, e)] + [b]
            gaps += [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        names = innermost(hosts, [(s + e) / 2 for s, e in gaps])
        for (s, e), name in zip(gaps, names):
            idle[name] += e - s
    n = max(1, len(planes))
    modules: dict = collections.Counter()
    for st in steps:
        st["module_ns"] = {k: v / n for k, v in st["module_ns"].items()}
        if st["covered"]:
            modules.update(st["module_ns"])
    return {
        "devices": len(planes),
        "window_ns": sum(b - a for a, b in covered),
        "dropped_ns": sum(b - a for a, b in dropped),
        "busy_ns": busy / n,
        "module_ns": dict(modules),
        "op_ns": {k: v / n for k, v in ops.items()},
        "idle_by_host_ns": {k: v / n for k, v in idle.items()},
        "steps": steps,
    }


def op_name(name: str) -> str:
    """An HLO op's name and the start of its shape, not its whole text."""
    head, _, rest = name.partition(" = ")
    return f"{head} = {rest[:60]}" if rest else head


def top(d: dict, k: int = 10) -> list:
    """[[name, seconds], ...] of the k largest, in seconds."""
    items = sorted(d.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in items]
