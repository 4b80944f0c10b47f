"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The harness wraps its measured window in a host annotation
``bench_window`` and each trial in ``bench_trial``; the program's own
spans (``iteration``, ``compute``, ``assemble``, ...) reach the same
trace through ``repro.obs``'s profiler bridge.  From the device planes
this module takes the operations of each chip used, and from them:

* busy time: the union of the intervals in which an operation ran,
  inside the window;
* time per operation name;
* idle gaps: the window minus the busy union, each attributed to the
  innermost host span open at the middle of the gap (``(none)`` where
  no span was open);
* compute time: the busy union of the operations that are not
  host/device transfers, for roofline shares.

An operation's name in the trace is its HLO text (``%fusion.1 = f32[..]
fusion(...), kind=kCustom, ...``); it is reported by its label,
``%fusion.1 (fusion kCustom)``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench_window"
TRIAL = "bench_trial"
NO_SPAN = "(none)"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: opcodes that move bytes between the host and the chip
TRANSFERS = {"infeed", "outfeed", "send", "send-done", "recv", "recv-done"}
_KIND = re.compile(r"kind=(k\w+)")


def parse_op(text: str) -> tuple[str, str]:
    """``(label, opcode)`` of an operation's HLO text; a text that is not
    HLO is its own label, with no opcode."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    depth, i = 0, 0
    for i, ch in enumerate(rest):        # skip the result shape
        depth += (ch == "(") - (ch == ")")
        if ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    kind = _KIND.search(rest)
    label = f"{name} ({opcode}{' ' + kind.group(1) if kind else ''})"
    return label, opcode


@dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclass
class Trace:
    device_ops: dict[int, list[Event]] = field(default_factory=dict)
    host: list[Event] = field(default_factory=list)


def load(path: str, host_names: set[str]) -> Trace:
    """The device operations of every TPU plane, and the host events
    named in ``host_names``, from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path), host_names)


def from_profile(prof, host_names: set[str]) -> Trace:
    names = set(host_names) | {WINDOW, TRIAL}
    tr = Trace()
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = tr.device_ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(Event(e.name, e.start_ns, e.end_ns)
                               for e in line.events if e.name in names)
    for ops in tr.device_ops.values():
        ops.sort(key=lambda e: e.start)
    tr.host.sort(key=lambda e: e.start)
    return tr


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals as sorted disjoint pieces."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(pieces, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in pieces
            if min(e, hi) > max(s, lo)]


def length(pieces) -> float:
    return float(sum(e - s for s, e in pieces))


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi)`` that no busy piece covers."""
    out, t = [], lo
    for s, e in clip(busy, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: list[Event], starts: list[float], t: float) -> str:
    """Name of the latest-starting span that is open at ``t``."""
    i = bisect.bisect_right(starts, t)
    while i > 0:
        i -= 1
        if spans[i].end > t:
            return spans[i].name
    return NO_SPAN


@dataclass
class Summary:
    window_s: float
    busy_s: float               # averaged over the chips used
    compute_s: float            # busy union of non-transfer operations
    op_s: dict[str, float]      # device seconds per operation name
    idle_by_span: dict[str, float]
    spans: dict[str, list[Event]]   # host spans inside the window, by name

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_within(self, name: str) -> tuple[float, int]:
        """Seconds chip 0 stood idle inside the spans called ``name``,
        and how many there were."""
        total = 0.0
        spans = self.spans.get(name, [])
        for ev in spans:
            total += ev.end - ev.start - length(
                clip(self._busy, ev.start, ev.end))
        return total * 1e-9, len(spans)

    _busy: list = field(default_factory=list, repr=False)


def summarize(tr: Trace, devices: int) -> Summary:
    """Reduce the trace over ``bench_window`` for chips ``0..devices-1``."""
    windows = [e for e in tr.host if e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    missing = [d for d in range(devices) if not tr.device_ops.get(d)]
    if missing:
        raise ValueError(f"no device operations traced on chips {missing}")
    inside = [e for e in tr.host if e.name != WINDOW
              and e.end > lo and e.start < hi]
    starts = [e.start for e in inside]
    busy_total = compute_total = 0.0
    op_s: dict[str, float] = defaultdict(float)
    idle_by_span: dict[str, float] = defaultdict(float)
    busy0: list = []
    for d in range(devices):
        ops = [e for e in tr.device_ops[d] if e.end > lo and e.start < hi]
        busy = clip(merge((e.start, e.end) for e in ops), lo, hi)
        busy_total += length(busy)
        parsed = [parse_op(e.name) for e in ops]
        compute_total += length(clip(merge(
            (e.start, e.end) for e, (_, opcode) in zip(ops, parsed)
            if opcode not in TRANSFERS), lo, hi))
        for e, (label, _) in zip(ops, parsed):
            op_s[label] += (min(e.end, hi) - max(e.start, lo)) * 1e-9
        for s, e in gaps(busy, lo, hi):
            idle_by_span[innermost(inside, starts, (s + e) / 2)] += (
                (e - s) * 1e-9 / devices)
        if d == 0:
            busy0 = busy
    spans: dict[str, list[Event]] = defaultdict(list)
    for e in inside:
        if e.start >= lo and e.end <= hi:
            spans[e.name].append(e)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total * 1e-9 / devices,
        compute_s=compute_total * 1e-9 / devices,
        op_s=dict(op_s),
        idle_by_span=dict(idle_by_span),
        spans=dict(spans),
        _busy=busy0,
    )


def top(d: dict[str, float], k: int = 10) -> list[list]:
    return [[name, s] for name, s in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
