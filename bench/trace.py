"""Reduction of a profiler trace to what the per-layer readers need.

``read_xspace`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace``: the device operations and the device's program (module) events
of each chip, and the harness's own spans (the window, each round, each
stretch between rounds), which it notes on the wall clock and which are
placed on the trace's clock by the profile's start time.  The host tracer
is off: the runtime's own host events come by the million (per-tile
transposes of the round stack), and a 51 s window of them did not fit in a
one-chip machine's 40 GiB.

The reductions are plain functions over ``Event`` lists, so a test can
build a trace by hand:

- ``busy_ns``: the union of the device operations' intervals inside the
  window, so that overlapping operations count once;
- ``idle_gaps``: each stretch of the window in which no operation ran on
  the device, labelled as the device's own where it lies inside one
  program execution, else by the host event that overlaps it most (the
  harness's own spans only where nothing narrower does);
- ``top_ops``: device time per kind of operation.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
ROUND = "bench.round"
BETWEEN = "bench.between"
# a gap is labelled by the host event that overlaps it most; the harness's
# own spans only where nothing narrower overlaps it, the window last
RANK = {WINDOW: 0, ROUND: 1, BETWEEN: 1}
# device operations that contain others; their time is their body's
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> operations
    modules: Dict[str, List[Event]]    # device plane -> program executions
    host: List[Event]
    window: Tuple[float, float]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def read_xspace(log_dir: str, spans) -> Trace:
    """``spans``: (name, start, end) in wall-clock ns, one of them the
    window."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops, modules, start = {}, {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(_events(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).extend(_events(line))
        elif plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    if start is None:
        raise RuntimeError("the trace has no profile_start_time")
    host = [Event(name, a - start, b - start) for name, a, b in spans]
    windows = [e for e in host if e.name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span in the trace, "
                           f"found {len(windows)}")
    return Trace(ops=ops, modules=modules, host=host,
                 window=(windows[0].start_ns, windows[0].end_ns))


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


@functools.lru_cache(maxsize=None)
def op_kind(name: str) -> str:
    """``%add_select_fusion.54 = (f32[...]) fusion(...)`` -> the
    instruction's name without its number: ``add_select_fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _clip(events, lo, hi):
    return sorted((max(e.start_ns, lo), min(e.end_ns, hi))
                  for e in events if e.end_ns > lo and e.start_ns < hi)


def merged(events, lo, hi) -> List[Tuple[float, float]]:
    """Union of the events' intervals inside [lo, hi], as sorted runs."""
    runs: List[List[float]] = []
    for s, e in _clip(events, lo, hi):
        if runs and s <= runs[-1][1]:
            runs[-1][1] = max(runs[-1][1], e)
        else:
            runs.append([s, e])
    return [(s, e) for s, e in runs]


def busy_ns(trace: Trace) -> float:
    """Device-busy time inside the window, averaged over the chips."""
    lo, hi = trace.window
    per_chip = [sum(e - s for s, e in merged(ev, lo, hi))
                for ev in trace.ops.values()]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def gaps(trace: Trace) -> List[Tuple[float, float]]:
    """Stretches of the window with no operation on the first chip."""
    lo, hi = trace.window
    plane = sorted(trace.ops)[0] if trace.ops else None
    runs = merged(trace.ops[plane], lo, hi) if plane else []
    out, cur = [], lo
    for s, e in runs:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


IN_PROGRAM = "device: between operations of one program"


def _inside(gaps_: np.ndarray, modules: List[Event]) -> np.ndarray:
    """Per gap (rows of start, end): whether one program execution spans
    it, that is, whether the latest end among the executions that start at
    or before the gap's start lies at or after the gap's end."""
    if not modules or not len(gaps_):
        return np.zeros(len(gaps_), bool)
    order = sorted(modules, key=lambda m: m.start_ns)
    starts = np.asarray([m.start_ns for m in order], np.float64)
    reach = np.maximum.accumulate(np.asarray([m.end_ns for m in order],
                                             np.float64))
    i = np.searchsorted(starts, gaps_[:, 0], side="right") - 1
    return (i >= 0) & (reach[np.maximum(i, 0)] >= gaps_[:, 1])


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds per label, the largest ``n``.  A gap inside one program
    execution is the device's own.  Any other goes to the host event name
    whose spans overlap it most, summed over the host's threads; only where
    no such event overlaps it, to the harness's innermost span."""
    plane = sorted(trace.ops)[0] if trace.ops else None
    modules = trace.modules.get(plane, [])
    names = sorted({e.name for e in trace.host})
    index = {name: i for i, name in enumerate(names)}
    starts = np.asarray([e.start_ns for e in trace.host], np.float64)
    ends = np.asarray([e.end_ns for e in trace.host], np.float64)
    ids = np.asarray([index[e.name] for e in trace.host], np.int64)
    rank = np.asarray([RANK.get(name, 2) for name in names])
    total: Dict[str, float] = defaultdict(float)
    all_gaps = np.asarray(gaps(trace), np.float64).reshape(-1, 2)
    inside = _inside(all_gaps, modules)
    if inside.any():
        total[IN_PROGRAM] = float(np.sum(np.diff(all_gaps[inside]))) * 1e-9
    for g in all_gaps[~inside]:
        label = "host"
        if len(ids):
            ov = np.clip(np.minimum(ends, g[1]) - np.maximum(starts, g[0]),
                         0, None)
            per = np.bincount(ids, weights=ov, minlength=len(names))
            if per.max() > 0:
                top = rank[per > 0].max()
                label = names[int(np.argmax(np.where(rank == top, per,
                                                     -1.0)))]
        total[label] += (g[1] - g[0]) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device seconds per kind of operation (``op_kind``) inside the window,
    the largest ``n``, averaged over the chips; containers are left out."""
    lo, hi = trace.window
    total: Dict[str, float] = defaultdict(float)
    for events in trace.ops.values():
        for e in events:
            ov = min(e.end_ns, hi) - max(e.start_ns, lo)
            kind = op_kind(e.name)
            if ov > 0 and kind not in CONTAINERS:
                total[kind] += ov * 1e-9 / len(trace.ops)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def module_ns(trace: Trace, prefix: str) -> float:
    """Device time of the program executions whose name starts with
    ``prefix``, inside the window, averaged over the chips."""
    lo, hi = trace.window
    per_chip = [sum(e - s for s, e in _clip(
        [m for m in ev if m.name.startswith(prefix)], lo, hi))
        for ev in trace.modules.values()]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0
