"""The reduction from a profiler trace to device busy and idle time, device
time by operation name, and idle time by the host span open during it.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` into plain event lists (:func:`load_events`),
or those lists themselves as JSON (a recorded trace: :func:`save_events`,
:func:`read_events`), so that the arithmetic can be checked without a chip.

* Device operations are the events on the ``XLA Ops`` line of each
  ``/device:`` plane, named by their HLO instruction (and, for a custom
  call, its target: ``name [tpu_custom_call]``). Busy time is the union
  of their intervals on each chip, averaged over the chips; idle share is
  one minus busy over the traced window (the benchmark's ``bench.window``
  annotation).
* Host spans are the ``TraceAnnotation`` events the broker's tracer writes
  when ``jax_annotations`` is on (``scheduler.flush``, ``broker.*``). Each
  part of an idle gap between device operations is charged to the innermost
  such span open during it, or to ``host.waiting`` where none is.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)

OPS_LINE = "XLA Ops"
NO_SPAN = "host.waiting"


@dataclass
class Events:
    device: Dict[str, List[Event]] = field(default_factory=dict)  # plane → ops
    host: List[Event] = field(default_factory=list)  # annotated host spans


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> str:
    """An ``XLA Ops`` event's name (the whole HLO instruction text) cut to
    the instruction's name, with a custom call's target."""
    if not hlo.startswith("%"):
        return hlo[:120]
    name = hlo[1:].split(" ", 1)[0]
    m = _TARGET.search(hlo)
    return f"{name} [{m.group(1)}]" if m else name


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load_events(path: str, host_prefixes: Sequence[str] = ("scheduler.", "broker.")) -> Events:
    """Device operations and annotated host spans of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ev = Events()
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ev.device.setdefault(plane.name, []).extend(
                        (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(host_prefixes)):
                        ev.host.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return ev


def save_events(ev: Events, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"device": ev.device, "host": ev.host}, f)


def read_events(path: str) -> Events:
    with open(path) as f:
        d = json.load(f)
    return Events(
        {k: [tuple(e) for e in v] for k, v in d["device"].items()},
        [tuple(e) for e in d["host"]],
    )


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float  # mean over chips
    ops_s: Dict[str, float]  # device time by op name, summed over chips
    idle_by_span: Dict[str, float]  # idle seconds by host span, mean over chips

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]]


def _timeline(host: List[Event]) -> List[Tuple[float, float, str]]:
    """The host's timeline as sorted, disjoint (start, end, name) segments,
    each named after the innermost span open over it (the window's own
    ``bench.`` annotation is not a host span). Spans nest; a child that
    outlasts its parent is cut at the parent's end."""
    spans = sorted(
        ((s, s + d, n) for n, s, d in host if not n.startswith("bench.")),
        key=lambda sp: (sp[0], -sp[1]),
    )
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []  # open spans: (end, name)
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
            t = max(t, end)

    for s, e, n in spans:
        close_until(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, n))
        t = max(t, s)
    close_until(float("inf"))
    return segs


def _charge(segs: List[Tuple[float, float, str]], starts: List[float],
            g0: float, g1: float, scale: float, out: Dict[str, float]) -> None:
    """Add the gap [g0, g1] (ns) to ``out`` (s × scale), split by segment."""
    covered = 0.0
    i = max(bisect.bisect_right(starts, g0) - 1, 0)
    while i < len(segs) and segs[i][0] < g1:
        a, b = max(segs[i][0], g0), min(segs[i][1], g1)
        if b > a:
            name = segs[i][2]
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9 * scale
            covered += b - a
        i += 1
    if g1 - g0 > covered:
        out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (g1 - g0 - covered) * 1e-9 * scale


def summarize(ev: Events, window: Tuple[float, float]) -> Summary:
    """Reduce the events inside ``window`` (start_ns, end_ns)."""
    w0, w1 = window
    segs = _timeline(ev.host)
    starts = [a for a, _, _ in segs]
    chips = max(len(ev.device), 1)
    busy = 0.0
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for plane, events in ev.device.items():
        clipped = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                _charge(segs, starts, g0, g1, 1.0 / chips, idle)
    if not ev.device:
        idle[NO_SPAN] = (w1 - w0) * 1e-9
    return Summary(chips, (w1 - w0) * 1e-9, busy / chips, ops, idle)


def kernel_seconds(summary: Summary, *needles: str) -> Optional[float]:
    """Device time of the operations whose name contains every needle
    (summed over chips), or None where no operation carries them."""
    hits = [v for k, v in summary.ops_s.items() if all(n in k for n in needles)]
    return sum(hits) if hits else None
