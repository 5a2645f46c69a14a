"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's readings.

What a TPU trace holds, as read by ``jax.profiler.ProfileData``: one plane per
chip (``/device:TPU:<n>``) whose line ``XLA Modules`` has an event per
program run (``jit_prefill(<fingerprint>)``) and whose line ``XLA Ops`` has an
event per executed HLO instruction, named by the instruction's text
(``%fusion.170 = f32[...] fusion(...), kind=..., calls=...``); and a host
plane (``/host:CPU``) whose lines carry the harness's own spans
(``jax.profiler.TraceAnnotation``). Times are nanoseconds from the trace's
start, on one clock for host and device.

The instruction events carry no ``jax.named_scope``: the scope lives in the
compiled program's metadata. So :func:`hlo_scopes` reads the compiled text of
a program (``compiled.as_text()``) and marks every instruction whose own
``op_name``, or any instruction fused into it, lies under a scope. A marked
instruction may hold some work outside the scope (a cache write fused into
the score product); its whole time is counted, so a share of the roofline
computed from it errs low, never high.

:func:`reduce` gives, over the window spanned by the harness's spans:

* busy and idle seconds of the device (union of instruction intervals);
* device seconds under each named scope (union of the marked instructions);
* the instructions that took most time;
* the idle gaps, each charged to the innermost harness span around it.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

SPAN_PREFIX = "chipbench."
_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_EVENT = re.compile(r"^%?([\w.\-]+)")


def hlo_scopes(text: str, scopes) -> tuple:
    """(module name, {instruction name: set of scopes}) of one compiled
    program's text, for the instructions that run as events of their own."""
    module = re.search(r"HloModule ([\w.\-]+)", text).group(1)
    comps, cur = {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            if cur:
                comps[cur] = []
        elif cur:
            comps[cur].append(line)

    def own(line):
        m = _OPNAME.search(line)
        return {s for s in scopes if m and s in m.group(1)}

    inner = {c: set().union(*(own(ln) for ln in lines)) if lines else set()
             for c, lines in comps.items()}
    marks = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            found = own(line)
            calls = _CALLS.search(line)
            if calls and "fusion(" in line:
                found |= inner.get(calls.group(1), set())
            if found:
                marks[m.group(1)] = found
    return module, marks


def _merged(starts, ends):
    """The union of intervals, as sorted disjoint (starts, ends)."""
    o = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, np.float64)[o], np.asarray(ends, np.float64)[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _union(starts, ends) -> float:
    """Length of the union of intervals."""
    if len(starts) == 0:
        return 0.0
    s, e = _merged(starts, ends)
    return float(np.sum(e - s))


@dataclass
class Reading:
    window_s: float
    busy_s: float                       # averaged over the chips with events
    scope_s: dict = field(default_factory=dict)
    top_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)
    n_chips: int = 0
    n_ops: int = 0

    @property
    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def reduce(path, programs=(), scopes=(), top=10) -> Reading:
    """Read the trace at ``path``. ``programs`` are compiled texts whose
    instructions are marked with ``scopes``."""
    from jax.profiler import ProfileData

    marks = {}
    for text in programs:
        module, m = hlo_scopes(text, scopes)
        marks.setdefault(module, {}).update(m)

    prof = ProfileData.from_file(str(path))
    spans = []
    chips = []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name[len(SPAN_PREFIX):]))
        elif re.match(r"/device:TPU:\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                chips.append(lines)
    rounds = [s for s in spans if s[2] == "round"]
    if not rounds:
        raise ValueError(f"no {SPAN_PREFIX}round span in {path}")
    w0 = min(s[0] for s in rounds)
    w1 = max(s[1] for s in rounds)

    busy, scope_s, op_time, gaps, n_ops, used = [], defaultdict(float), defaultdict(float), defaultdict(float), 0, 0
    for lines in chips:
        mods = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                       for ev in lines["XLA Modules"].events), key=lambda t: t[0]) \
            if "XLA Modules" in lines else []
        mod_starts = np.array([m[0] for m in mods])
        st, en, per_scope = [], [], defaultdict(lambda: ([], []))
        for ev in lines["XLA Ops"].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            st.append(s)
            en.append(e)
            name = _EVENT.match(ev.name).group(1)
            k = int(np.searchsorted(mod_starts, s, side="right")) - 1
            module = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
            found = marks.get(module, {}).get(name, ())
            for sc in found:
                per_scope[sc][0].append(s)
                per_scope[sc][1].append(e)
            if " while(" not in ev.name:  # a loop's event spans its body's
                label = f"{module}:{name}" + (f" [{','.join(sorted(found))}]" if found else "")
                op_time[label] += (e - s) * 1e-9
        if not st:
            continue
        used += 1
        n_ops += len(st)
        busy.append(_union(st, en) * 1e-9)
        for sc, (a, b) in per_scope.items():
            scope_s[sc] += _union(a, b) * 1e-9
        ms, me = _merged(st, en)
        g0, g1 = np.concatenate([[w0], me]), np.concatenate([ms, [w1]])
        keep = g1 > g0
        g0, g1 = g0[keep], g1[keep]
        mid = 0.5 * (g0 + g1)
        # charge each gap to the shortest span around its middle
        best, owner = np.full(len(mid), np.inf), np.full(len(mid), -1)
        for i, (a, b, _) in enumerate(spans):
            sel = (mid >= a) & (mid <= b) & (b - a < best)
            best[sel], owner[sel] = b - a, i
        for i in np.unique(owner):
            name = spans[i][2] if i >= 0 else "outside spans"
            gaps[name] += float(np.sum((g1 - g0)[owner == i])) * 1e-9
    if not used:
        raise ValueError(f"no device operation inside the window in {path}")
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reading(window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / used,
                   scope_s={k: v / used for k, v in scope_s.items()},
                   top_ops=[[k, v] for k, v in top_ops],
                   idle_gaps=[[k, v / used] for k, v in
                              sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
                   n_chips=used, n_ops=n_ops)
