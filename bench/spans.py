"""The program's own spans and named scopes on the device trace's clock.

``repro.common.spans`` records host spans of the round loop (``fl.round``
and its parts) on the wall clock, the clock the harness notes its own spans
on; they are placed on the trace by the same offset (``place``).  The
round program's ``jax.named_scope``\\ s (``neulite.prefix``, ``.block``,
``.mentor``, ``.optimizer``, ``.eq1``) live in the op_name metadata of the
compiled HLO; the trace names each device operation by its HLO text
without metadata, so ``scope_table`` reads the scope of every instruction
from the compiled program's ``as_text()``.

The reductions, plain functions a test can feed by hand:

- ``idle_labels``: each stretch of the window in which no operation ran on
  the device, outside the program executions, cut at the edges of the host
  spans and labelled by the innermost span over each piece (program spans
  by depth above the harness's); where a gap ends at the start of a round
  program execution, its part after that round's ``fl.dispatch`` began is
  ``fl.input_wait``: the program was waiting for its inputs;
- ``clock_violation_ms``: the worst amount by which a round program's
  execution starts before its ``fl.dispatch`` began or ends after its
  ``fl.sync`` ended;
- ``scope_seconds``: device time of the round program per scope, each
  instant counted once;
- ``self_ms``: host self time of each span name per round.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from bench import trace as tr

PROGRAM = "jit_round_fn"
DISPATCH = "fl.dispatch"
SYNC = "fl.sync"
ROUND = "fl.round"
INPUT_WAIT = "fl.input_wait"
UNSCOPED = "unscoped"
SCOPES = ("prefix", "block", "mentor", "optimizer", "eq1")
# a gap "ends where an execution starts" when the execution starts inside
# it: its event opens a few ns before its first operation, and may round to
# just after the gap's end
START_SLACK_NS = 10
# an operation may end this much after its execution's event, by rounding
END_SLACK_NS = 10


@dataclasses.dataclass(frozen=True)
class HostSpan(tr.Event):
    depth: int = 0


def place(recorded, offset_ns: float) -> List[HostSpan]:
    """The recorder's closed spans on the trace's clock (wall clock minus
    ``offset_ns``), each with its depth in the span tree."""
    depth: List[int] = []
    out = []
    for s in recorded:
        depth.append(0 if s.parent is None else depth[s.parent] + 1)
        if s.end_ns:
            out.append(HostSpan(s.name, s.start_ns - offset_ns,
                                s.end_ns - offset_ns, depth[-1]))
    return out


def _rank(e: tr.Event) -> int:
    if isinstance(e, HostSpan):
        return 3 + e.depth
    return tr.RANK.get(e.name, 2)


def _executions(trace: tr.Trace) -> List[tr.Event]:
    plane = sorted(trace.modules)[0] if trace.modules else None
    return sorted((m for m in trace.modules.get(plane, [])
                   if m.name.startswith(PROGRAM)), key=lambda m: m.start_ns)


def idle_labels(trace: tr.Trace, spans: Iterable[HostSpan]
                ) -> Dict[str, float]:
    """Idle seconds per label: the device's own inside one program
    execution (``tr.IN_PROGRAM``), ``fl.input_wait``, else the innermost
    host span over each piece of a gap (the harness's spans, then
    ``host``, where no program span covers it)."""
    spans = list(spans)
    host = list(trace.host) + spans
    plane = sorted(trace.ops)[0] if trace.ops else None
    all_gaps = np.asarray(tr.gaps(trace), np.float64).reshape(-1, 2)
    inside = tr._inside(all_gaps, trace.modules.get(plane, []))
    total: Dict[str, float] = defaultdict(float)
    if inside.any():
        total[tr.IN_PROGRAM] = float(np.sum(np.diff(all_gaps[inside]))) * 1e-9
    execs = _executions(trace)
    starts = np.asarray([m.start_ns for m in execs], np.float64)
    dispatch = sorted(s.start_ns for s in spans if s.name == DISPATCH)
    for lo, hi in all_gaps[~inside]:
        cut = hi
        i = int(np.searchsorted(starts, lo))
        if i < len(execs) and execs[i].start_ns <= hi + START_SLACK_NS:
            j = int(np.searchsorted(dispatch, hi, side="right")) - 1
            if j >= 0 and dispatch[j] < hi:
                cut = max(lo, dispatch[j])
                total[INPUT_WAIT] += (hi - cut) * 1e-9
        for a, b, label in _pieces(host, lo, cut):
            total[label] += (b - a) * 1e-9
    return dict(total)


def _pieces(host: List[tr.Event], lo: float, hi: float
            ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut at the edges of the host events over it, each piece
    labelled by the highest-ranked event that covers it."""
    if hi <= lo:
        return []
    over = [e for e in host if e.end_ns > lo and e.start_ns < hi]
    edges = sorted({lo, hi, *(min(max(x, lo), hi) for e in over
                              for x in (e.start_ns, e.end_ns))})
    out = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        cover = [e for e in over if e.start_ns <= mid < e.end_ns]
        label = (max(cover, key=_rank).name if cover else "host")
        out.append((a, b, label))
    return out


def clock_violation_ms(trace: tr.Trace, spans: Iterable[HostSpan]
                       ) -> Optional[float]:
    """The i-th round program execution in the window against the i-th
    ``fl.dispatch`` and the first ``fl.sync`` after it: how far the
    execution starts before the dispatch began or ends after the sync
    ended, the worst over the rounds, in ms (0 when none does)."""
    lo, hi = trace.window
    spans = list(spans)
    execs = [m for m in _executions(trace) if lo <= m.start_ns <= hi]
    dispatch = sorted((s for s in spans if s.name == DISPATCH),
                      key=lambda s: s.start_ns)
    syncs = sorted((s for s in spans if s.name == SYNC),
                   key=lambda s: s.start_ns)
    if not execs or not dispatch:
        return None
    if len(execs) != len(dispatch):
        raise ValueError(f"{len(execs)} {PROGRAM} executions in the window "
                         f"against {len(dispatch)} {DISPATCH} spans")
    worst = 0.0
    for m, d in zip(execs, dispatch):
        s = next((s for s in syncs if s.start_ns >= d.start_ns), None)
        late = 0.0 if s is None else m.end_ns - s.end_ns
        worst = max(worst, d.start_ns - m.start_ns, late)
    return worst * 1e-6


# --------------------------------------------------------------------------- #
# named scopes
# --------------------------------------------------------------------------- #
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_SCOPE = re.compile(r"neulite\.(\w+)")


def _head(rest: str) -> str:
    """``<type> <opcode>`` of an instruction's text after ``name = ``: the
    part the trace's operation names share with ``as_text()``."""
    depth, i = 0, 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    call = rest.find("(", i)
    return rest[:call if call >= 0 else len(rest)]


@functools.lru_cache(maxsize=None)
def op_key(name: str) -> Tuple[str, str]:
    """A trace operation's name -> (instruction name, ``<type> <opcode>``)."""
    head, _, rest = name.partition(" = ")
    return head.lstrip("%"), _head(rest)


def scope_table(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> (``<type> <opcode>``, scope) for one compiled
    module.  The scope is the last ``neulite.<scope>`` of the instruction's
    op_name; a fusion takes its root's scope (its own op_name where the
    root has none); an instruction with no metadata at all (an
    asynchronous copy, a bitcast) takes its nearest user's; one whose
    op_name names no scope is ``unscoped``."""
    instr: Dict[str, Tuple[str, Optional[str], Optional[str]]] = {}
    users: Dict[str, List[str]] = defaultdict(list)
    roots: Dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        body, _, meta = rest.partition(", metadata={")
        op = re.search(r'op_name="([^"]*)"', meta)
        calls = re.search(r"calls=%([\w.\-]+)", body)
        instr[name] = (_head(rest), op.group(1) if op else None,
                       calls.group(1) if calls else None)
        if line.lstrip().startswith("ROOT ") and comp is not None:
            roots[comp] = name
        operands = body[body.find("(") + 1:]
        for used in re.findall(r"%([\w.\-]+)", operands):
            users[used].append(name)

    memo: Dict[str, Optional[str]] = {}

    def own(name: str, seen: frozenset) -> Optional[str]:
        if name in memo:
            return memo[name]
        if name in seen or name not in instr:
            return None
        seen = seen | {name}
        _, op, calls = instr[name]
        scope = None
        if calls is not None and calls in roots:
            scope = own(roots[calls], seen)
        if scope is None and op is not None:
            found = _SCOPE.findall(op)
            scope = found[-1] if found else UNSCOPED
        if scope is None:
            for u in users.get(name, ()):
                scope = own(u, seen)
                if scope is not None:
                    break
        memo[name] = scope
        return scope

    return {name: (head, own(name, frozenset()) or UNSCOPED)
            for name, (head, _, _) in instr.items()}


def scope_seconds(trace: tr.Trace, tables: List[Dict[str, Tuple[str, str]]]
                  ) -> Dict[str, float]:
    """Device seconds per scope of the round program executions inside the
    window, on the first chip.  Each execution is matched to the compiled
    module whose instructions agree with most of its operations' names;
    an operation the module does not know is ``unscoped``.  Operations
    run one after another on a core; where two still overlap, each instant
    is counted once, for the one that started first.  Containers (``while``
    and the like) are left out: their time is their body's."""
    lo, hi = trace.window
    plane = sorted(trace.ops)[0] if trace.ops else None
    execs = [m for m in _executions(trace) if m.end_ns > lo
             and m.start_ns < hi]
    if not execs or not tables:
        return {}
    starts = np.asarray([m.start_ns for m in execs], np.float64)
    by_exec: Dict[int, List[tr.Event]] = defaultdict(list)
    for e in trace.ops.get(plane, []):
        i = int(np.searchsorted(starts, e.start_ns, side="right")) - 1
        if i >= 0 and e.end_ns <= execs[i].end_ns + END_SLACK_NS \
                and tr.op_kind(e.name) not in tr.CONTAINERS:
            by_exec[i].append(e)
    table_of: Dict[str, Dict[str, Tuple[str, str]]] = {}
    for i, ops in by_exec.items():
        module = execs[i].name
        if module not in table_of:
            keys = {op_key(e.name) for e in ops}
            table_of[module] = max(tables, key=lambda t: sum(
                t.get(n, ("", ""))[0] == h for n, h in keys))
    total: Dict[str, float] = defaultdict(float)
    for i, ops in by_exec.items():
        table = table_of[execs[i].name]
        cursor = lo
        for e in sorted(ops, key=lambda e: e.start_ns):
            a, b = max(e.start_ns, cursor, lo), min(e.end_ns, hi)
            if b > a:
                name, head = op_key(e.name)
                want, scope = table.get(name, (None, UNSCOPED))
                total[scope if want == head else UNSCOPED] += (b - a) * 1e-9
            cursor = max(cursor, e.end_ns)
    return dict(total)


def self_ms(recorded) -> Dict[str, float]:
    """Host self time (a span's time less its children's) per span name,
    summed over the recorder's spans and divided by its ``fl.round``
    count, in ms."""
    own = [s.end_ns - s.start_ns for s in recorded]
    for s in recorded:
        if s.parent is not None:
            own[s.parent] -= s.end_ns - s.start_ns
    rounds = sum(1 for s in recorded if s.name == ROUND)
    total: Dict[str, float] = defaultdict(float)
    for s, ns in zip(recorded, own):
        total[s.name] += ns * 1e-6 / max(rounds, 1)
    return dict(total)
