"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` (nothing but JAX). On a TPU the
trace holds, per chip, a plane ``/device:TPU:<n>`` whose ``XLA Modules``
line has one event per program run (``jit_<name>(<hash>)``) and whose
``XLA Ops`` line has one event per HLO op run (named by the op's HLO text,
``%name = <type> <kind>(...)...``); ``Async XLA Ops`` are copies in
flight, which overlap compute and are left out. Host planes
(``/host:CPU``) hold one line per thread with the benchmark's
``TraceAnnotation`` spans. All events share one clock.

* busy: the union of the op intervals inside the window, per chip;
* per-op device time: summed by op (``<program>/<op name> <kind>``),
  leaving out the ops that only contain others (``while``, ``call``,
  ``conditional``), whose time their contents already count;
* idle gaps: the intervals of the window in which no op ran, each
  labelled by the innermost benchmark span open on the host at its
  midpoint (``no_span`` when none was).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

CONTAINERS = ("while", "call", "conditional")
_KIND = re.compile(r"^%(\S+) = .*?\s([a-z][a-z0-9\-]*)\(")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass
class Op:
    module: str     # program, e.g. "jit__paged_step_impl"
    name: str       # op name without the leading "%"
    kind: str       # HLO op kind, e.g. "fusion", "custom-call"
    text: str       # the full HLO text of the op
    t0: float       # ns
    t1: float


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]            # ns
    busy_ns: Dict[str, float]              # device -> busy inside window
    ops: List[Op]                          # leaf ops inside the window
    gaps: List[Tuple[float, float, str]]   # (t0, t1, host span label)
    spans: List[Tuple[str, float, float, dict]]  # host spans (name, t0, t1, args)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips in the trace."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def op_seconds(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for o in self.ops:
            key = f"{o.module}/{o.name} {o.kind}"
            out[key] = out.get(key, 0.0) + (o.t1 - o.t0) / 1e9
        return out

    def top_ops(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.op_seconds().items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        """The longest idle gaps, as [label, seconds]."""
        g = sorted(self.gaps, key=lambda x: x[0] - x[1])[:n]
        return [[lab, (t1 - t0) / 1e9] for t0, t1, lab in g]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _label(spans, t: float) -> str:
    best: Optional[tuple] = None
    for name, a, b, _ in spans:
        if a <= t <= b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "no_span"


def reduce(path: str, window_span: str, span_names) -> Reduced:
    """Reduce the trace at ``path``. The window is the host span named
    ``window_span``; ``span_names`` are the host spans that label gaps."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = []
    devices = {}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
        elif plane.name.startswith("/device:TPU"):
            devices[plane.name] = {ln.name: list(ln.events)
                                   for ln in plane.lines}
    win = [(a, b) for n, a, b, _ in spans if n == window_span]
    if not win:
        raise ValueError(f"no host span {window_span!r} in {path}")
    w0, w1 = win[0]
    spans = [sp for sp in spans if sp[0] in span_names]
    busy, ops, gaps = {}, [], []
    for dev, lines in devices.items():
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _MODULE.match(e.name).group(1))
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        ivs = []
        for e in lines.get("XLA Ops", []):
            a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            m = _KIND.match(e.name)
            name, kind = (m.group(1), m.group(2)) if m else (e.name, "?")
            if kind in CONTAINERS:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = mods[i][2] if i >= 0 and e.start_ns <= mods[i][1] else "?"
            ops.append(Op(mod, name, kind, e.name, a, b))
        u = _union(ivs)
        busy[dev] = sum(b - a for a, b in u)
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b, _label(spans, (a + b) / 2)))
    return Reduced((w0, w1), busy, ops, gaps, spans)
