"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy intervals, kernel events, idle gaps, and the
benchmark's own host spans that the gaps fall in.

Planes named ``/device:<KIND>:<n>`` are devices; their op line
(``XLA Ops``) holds one event per operation that ran. Host spans are
the benchmark's ``TraceAnnotation`` events (``window``,
``ingest.wait``, ``dispatch``, ``sync``, ``drain``) on any ``/host:``
plane. All times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OP_LINE = "XLA Ops"
WINDOW_SPAN = "window"
SPANS = ("ingest.wait", "dispatch", "sync", "drain")


def merge_intervals(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of ``[start, end)`` intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def total(iv) -> float:
    return float(sum(e - s for s, e in iv))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi)`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            acc += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def self_times(events) -> list[tuple[str, float]]:
    """``(name, self time)`` of each event of one line: its duration less
    the durations of the events nested directly in it (a TPU trace puts
    a ``while`` op's body ops inside the ``while`` op's span)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start, -events[i].end))
    own = [e.end - e.start for e in events]
    stack: list[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= events[stack[-1]].end:
            own[stack[-1]] -= e.end - e.start
        stack.append(i)
    return [(e.name, t) for e, t in zip(events, own)]


@dataclass
class Event:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    window: tuple[float, float]
    ops: dict[int, list[Event]]                 # device id → op events in window
    spans: dict[str, list[tuple[float, float]]] = field(default_factory=dict)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, device: int) -> list[tuple[float, float]]:
        return merge_intervals([(e.start, e.end) for e in self.ops[device]])

    def busy_ns(self) -> float:
        """Busy time averaged over the devices."""
        return sum(total(self.busy(d)) for d in self.ops) / max(len(self.ops), 1)

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns() / self.window_ns

    def idle_in_span(self, name: str) -> float:
        """Idle ns that fall inside host spans ``name``, averaged over
        the devices."""
        spans = merge_intervals(self.spans.get(name, []))
        acc = 0.0
        for d in self.ops:
            acc += overlap(gaps(self.busy(d), *self.window), spans)
        return acc / max(len(self.ops), 1)

    def op_time(self, match) -> float:
        """Device ns of the ops whose name ``match`` accepts, averaged
        over the devices."""
        acc = sum(e.end - e.start for evs in self.ops.values() for e in evs
                  if match(e.name))
        return acc / max(len(self.ops), 1)

    def top_ops(self, n: int = 10) -> list[list]:
        """The ops that took most device time, by self time (a loop op's
        span less the ops nested in it), named by the HLO op's name,
        averaged over the devices."""
        acc: dict[str, float] = {}
        for evs in self.ops.values():
            for name, t in self_times(evs):
                short = name.split(" = ")[0]
                acc[short] = acc.get(short, 0.0) + t
        k = max(len(self.ops), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k / 1e9] for name, ns in top]

    def top_gaps(self, n: int = 10) -> list[list]:
        """The longest idle gaps (first device), each named by the host
        span that covers most of it (``other`` where none does)."""
        dev = min(self.ops)
        merged = {k: merge_intervals(v) for k, v in self.spans.items()}
        out = []
        for s, e in gaps(self.busy(dev), *self.window):
            best, name = 0.0, "other"
            for k, iv in merged.items():
                o = overlap([(s, e)], iv)
                if o > best:
                    best, name = o, k
            out.append([name, (e - s) / 1e9])
        return sorted(out, key=lambda g: -g[1])[:n]


def find_xplane(directory: str | Path) -> Path:
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def reduce(profile, devices: set[int] | None = None) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace` over the
    host span ``window`` (the whole trace where there is none)."""
    ops: dict[int, list[Event]] = {}
    spans: dict[str, list[tuple[float, float]]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            evs = ops.setdefault(dev, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    evs.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in SPANS:
                        spans.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    if not ops:
        raise ValueError("the trace holds no device plane with op events")
    if spans.get(WINDOW_SPAN):
        lo = min(s for s, _ in spans[WINDOW_SPAN])
        hi = max(e for _, e in spans[WINDOW_SPAN])
    else:
        every = [(e.start, e.end) for evs in ops.values() for e in evs]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    ops = {d: [Event(e.name, max(e.start, lo), min(e.end, hi)) for e in evs
               if e.end > lo and e.start < hi] for d, evs in ops.items()}
    spans = {k: clip(v, lo, hi) for k, v in spans.items() if k != WINDOW_SPAN}
    return Trace(window=(lo, hi), ops=ops, spans=spans)


def load(path: str | Path, devices: set[int] | None = None) -> Trace:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)), devices)
