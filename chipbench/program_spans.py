#!/usr/bin/env python3
"""The program's own spans in a profiler trace, put beside the device's time.

    python3 chipbench/program_spans.py <trace.xplane.pb>

prints one JSON object: the reading below, for a trace of the served path
(``jax.profiler.start_trace`` around ``RealEngine.generate`` calls, or a
``--trace 1`` run of the benchmark).

The program writes host spans whose names start with ``PROGRAM_PREFIX``
(``repro.engine.generate`` around ``.prefill``, ``.grow_cache`` and
``.decode``; per decode iteration a ``.step`` around ``.sample``, ``.sync``
and ``.dispatch``). :func:`reduce` reads them together with the harness's
``chipbench.*`` spans and gives, over the window (the harness's rounds, or
without them the program's ``generate`` calls):

* ``idle_gaps``: the device's idle time, each gap charged to the innermost
  span of either family around its middle (:func:`charge_gaps`). Harness
  spans keep their stripped names (``round``, ``generate``, ``head``),
  program spans their full names, so the two never collide;
* ``module_s``: device busy seconds per program (``jit_decode_step``,
  ``jit_prefill``, ...): the union of the intervals of the instructions that
  start while that program runs;
* ``spans``: per program span name, ``n`` (count), ``s`` (host seconds),
  ``idle_s`` (device idle seconds inside them) and ``programs`` (device
  programs that start inside them).

Device quantities are averaged over the chips with events, as in
``trace.py``, which this module leaves as it is and reuses.

The device's clock in a TPU trace runs apart from the host's by about a
millisecond (1.16-1.46 ms early in the committed fixture), which is the size
of the host loop's own spans. :func:`clock_skew` bounds it from causality:
the host's n-th program launch (``PJRT_LoadedExecutable_Execute``) starts
before the device starts its n-th program, and the host learns that the
program is done (``tpu::System::Execute=>Done``) after it ends. On one chip
the device's events are moved by the middle of those bounds
(``skew_ns``, with half their width in ``skew_err_ns``) before any gap is
charged; where the bounds cannot be had (several chips, other event names,
unequal counts, a launch cut off at the trace's edge) nothing is moved, both
are None, and :func:`reduce` warns (``UncorrectedSkew``) on stderr: the
split among sub-millisecond spans is then not to be trusted.
"""

from __future__ import annotations

import json
import re
import sys
import warnings
from collections import defaultdict
from dataclasses import asdict, dataclass, field
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.trace import SPAN_PREFIX as HARNESS_PREFIX  # noqa: E402
from chipbench.trace import _merged  # noqa: E402

# the prefix of every span the program writes (``repro.serving.engine``)
PROGRAM_PREFIX = "repro."
LAUNCH = "PJRT_LoadedExecutable_Execute"
DONE = "tpu::System::Execute=>Done"
OUTSIDE = "outside spans"


class UncorrectedSkew(UserWarning):
    """The device's clock could not be put onto the host's."""


def charge_gaps(gap_starts, gap_ends, spans) -> dict:
    """{span name: total length of the gaps charged to it}. Each gap
    ``[gap_starts[i], gap_ends[i])`` (sorted, disjoint) goes to the shortest
    of ``spans`` — (start, end, name) — that holds its middle, ties to the
    first listed; a gap that no span holds goes to ``"outside spans"``."""
    g0, g1 = np.asarray(gap_starts, np.float64), np.asarray(gap_ends, np.float64)
    mid = 0.5 * (g0 + g1)
    owner = np.full(len(mid), -1)
    # longest first, so that a shorter span around the same middle wins
    order = sorted(range(len(spans)), key=lambda i: (-(spans[i][1] - spans[i][0]), -i))
    for i in order:
        a, b = spans[i][0], spans[i][1]
        owner[np.searchsorted(mid, a, "left"):np.searchsorted(mid, b, "right")] = i
    out = defaultdict(float)
    for i in np.unique(owner):
        name = spans[i][2] if i >= 0 else OUTSIDE
        out[name] += float(np.sum((g1 - g0)[owner == i]))
    return dict(out)


def clock_skew(dev_starts, dev_ends, launch_starts, done_starts):
    """(middle, half width) in ns of the interval that causality leaves for
    the device's clock minus the host's, when the n-th launch, the n-th
    device program and the n-th completion belong together. None when the
    counts differ or the bounds cross (then they do not belong together)."""
    n = len(dev_starts)
    if n == 0 or len(launch_starts) != n or len(done_starts) != n:
        return None
    ds, de = np.sort(dev_starts), np.sort(dev_ends)
    hi = float(np.min(ds - np.sort(launch_starts)))
    lo = float(np.max(de - np.sort(done_starts)))
    return (0.5 * (lo + hi), 0.5 * (hi - lo)) if lo <= hi else None


def _busy_upto(ms, me, t):
    """Busy time in [-inf, t) of the disjoint sorted intervals (ms, me)."""
    t = np.asarray(t, np.float64)
    full = np.concatenate([[0.0], np.cumsum(me - ms)])
    k = np.searchsorted(ms, t, side="right")
    last = np.maximum(k - 1, 0)
    part = np.where(k > 0, np.clip(t - ms[last], 0.0, (me - ms)[last]), 0.0)
    return full[last] + part


@dataclass
class ProgramReading:
    window_s: float
    busy_s: float                                   # averaged over the chips
    skew_ns: float | None = None                    # device minus host clock
    skew_err_ns: float | None = None                # half the width of its bounds
    idle_gaps: list = field(default_factory=list)   # [[span, s]], largest first
    module_s: dict = field(default_factory=dict)    # program -> busy seconds
    spans: dict = field(default_factory=dict)       # program span -> n, s, idle_s, programs
    n_chips: int = 0


def _host(prof):
    """(spans, launch starts, completion starts) of the host planes."""
    spans, launches, done = [], [], []
    for plane in prof.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name, s = ev.name, ev.start_ns
                if name.startswith(HARNESS_PREFIX):
                    spans.append((s, s + ev.duration_ns, name[len(HARNESS_PREFIX):]))
                elif name.startswith(PROGRAM_PREFIX):
                    spans.append((s, s + ev.duration_ns, name))
                elif name == LAUNCH:
                    launches.append(s)
                elif name == DONE:
                    done.append(s)
    return spans, launches, done


def reduce(path) -> ProgramReading:
    """Read the trace at ``path``."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(path))
    spans, launches, done = _host(prof)
    chips = []
    for plane in prof.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                chips.append(lines)
    outer = ([s for s in spans if s[2] == "round"]
             or [s for s in spans if s[2] == PROGRAM_PREFIX + "engine.generate"])
    if not outer:
        raise ValueError(f"no {HARNESS_PREFIX}round or {PROGRAM_PREFIX}engine.generate "
                         f"span in {path}")
    w0, w1 = min(s[0] for s in outer), max(s[1] for s in outer)
    program = defaultdict(list)
    for a, b, name in spans:
        if name.startswith(PROGRAM_PREFIX) and b > w0 and a < w1:
            program[name].append((max(a, w0), min(b, w1)))

    skew = err = None
    busy, gaps, module_s, used = [], defaultdict(float), defaultdict(float), 0
    span_idle, span_programs = defaultdict(float), defaultdict(float)
    for lines in chips:
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name.split("(")[0])
                      for ev in lines["XLA Modules"].events) if "XLA Modules" in lines else []
        if len(chips) == 1:
            skew, err = clock_skew([m[0] for m in mods], [m[1] for m in mods],
                                   launches, done) or (None, None)
        shift = -(skew or 0.0)
        ops = np.array([(ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in lines["XLA Ops"].events], np.float64).reshape(-1, 2)
        st, en = np.clip(ops[:, 0] + shift, w0, w1), np.clip(ops[:, 1] + shift, w0, w1)
        keep = en > st
        if not keep.any():
            continue
        used += 1
        ms, me = _merged(st[keep], en[keep])
        busy.append(float(np.sum(me - ms)) * 1e-9)
        g0, g1 = np.concatenate([[w0], me]), np.concatenate([ms, [w1]])
        open_ = g1 > g0
        for name, t in charge_gaps(g0[open_], g1[open_], spans).items():
            gaps[name] += t * 1e-9
        # each instruction belongs to the program running when it starts
        m0 = np.array([m[0] for m in mods], np.float64) + shift
        m1 = np.array([m[1] for m in mods], np.float64) + shift
        k = np.searchsorted(m0, ops[:, 0] + shift, side="right") - 1
        mine = (k >= 0) & keep
        mine[mine] = ops[mine, 0] + shift < m1[k[mine]]
        names = np.array([m[2] for m in mods] or [""])
        for name in np.unique(names[k[mine]]):
            sel = mine.copy()
            sel[mine] = names[k[mine]] == name
            s, e = _merged(st[sel], en[sel])
            module_s[str(name)] += float(np.sum(e - s)) * 1e-9
        starts = m0[(m0 >= w0) & (m0 < w1)]
        for name, iv in program.items():
            a, b = np.array(iv, np.float64).T
            inside = _busy_upto(ms, me, b) - _busy_upto(ms, me, a)
            span_idle[name] += float(np.sum(b - a - inside)) * 1e-9
            span_programs[name] += float(np.sum(np.searchsorted(starts, b, "left")
                                                - np.searchsorted(starts, a, "left")))
    if not used:
        raise ValueError(f"no device operation inside the window in {path}")
    if skew is None:
        warnings.warn(f"{path}: the device's clock skew could not be bounded on "
                      f"{used} chip(s) ({len(launches)} launches, {len(done)} "
                      "completions); gaps are charged on the uncorrected clock, "
                      "so the split among sub-millisecond spans may be wrong",
                      UncorrectedSkew, stacklevel=2)
    return ProgramReading(
        window_s=(w1 - w0) * 1e-9, busy_s=sum(busy) / used, skew_ns=skew, skew_err_ns=err,
        idle_gaps=[[k, v / used] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])],
        module_s={k: v / used for k, v in sorted(module_s.items(), key=lambda kv: -kv[1])},
        spans={name: {"n": len(iv),
                      "s": float(sum(b - a for a, b in iv)) * 1e-9,
                      "idle_s": span_idle[name] / used,
                      "programs": span_programs[name] / used}
               for name, iv in sorted(program.items())},
        n_chips=used)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit(__doc__.split("\n\n")[1])
    print(json.dumps(asdict(reduce(argv[0]))))


if __name__ == "__main__":
    main()
