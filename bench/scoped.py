"""The program's own names in a traced run: device seconds per kernel
scope, chip idle inside the main thread's program spans, and the spans
the program recorded before the window.

The program names its compiled kernels with ``jax.named_scope``
(``sparse``, ``dense``, ``post``, ``fold``; PageRank's ``gather`` and
``scatter`` inside ``sparse``).  A scope reaches the device trace as the
``tf_op`` stat of each operation's event metadata, such as
``jit(step)/sparse/gather/jit(_where)/select_n``.  JAX's ``ProfileData``
does not expose metadata stats, so this module reads the ``.xplane.pb``
itself, with a decoder for the few XSpace fields it needs built on
``google.protobuf``.  An operation's scope is the innermost of
:data:`SCOPES` on its path, leaving out the last element, which names
the operation; ``(unscoped)`` where there is none.

The program's host spans (``repro.obs``, bridged into the trace as
profiler annotations) lie on the line of the thread that recorded them.
The main thread's line is the one that holds the harness's
``bench_window``.

The names reach the trace from the compiled executable.  The program
keys JAX's persistent compilation cache on metadata while its spans
reach the profiler (``repro.obs``), so a traced run never takes a step
compiled by code that named other scopes from a shared cache.

The readers return None where the program names nothing: a program
without scopes reads ``(unscoped)`` throughout, and one without the
spans records no ``stage_wait``, ``host_wait`` or ``calibrate``.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
from collections import defaultdict
from dataclasses import dataclass

from bench import trace as tr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the program's kernel scopes, outermost first
SCOPES = ("sparse", "dense", "post", "fold", "gather", "scatter")
UNSCOPED = "(unscoped)"
#: spans the program records on its main thread
MAIN_SPANS = {"iteration", "compute", "device_put", "stage_wait",
              "host_wait", "calibrate", "split_refresh", "plan_waves",
              "host_lane_build", "checkpoint", "collective", "prepare"}
#: the ``(message, [(field, number, type, repeated, message type)])``
#: subset of the XSpace schema (``tsl/profiler/protobuf/xplane.proto``)
#: that is read here
_SCHEMA = (
    ("XSpace", [("planes", 1, "message", True, "XPlane")]),
    ("XPlane", [("name", 2, "string", False, None),
                ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True,
                 "XPlane.EventMetadataEntry"),
                ("stat_metadata", 5, "message", True,
                 "XPlane.StatMetadataEntry")]),
    ("XLine", [("name", 2, "string", False, None),
               ("timestamp_ns", 3, "int64", False, None),
               ("events", 4, "message", True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, "int64", False, None),
                ("offset_ps", 2, "int64", False, None),
                ("duration_ps", 3, "int64", False, None),
                ("stats", 4, "message", True, "XStat")]),
    ("XStat", [("metadata_id", 1, "int64", False, None),
               ("str_value", 5, "string", False, None),
               ("ref_value", 7, "uint64", False, None)]),
    ("XEventMetadata", [("name", 2, "string", False, None),
                        ("stats", 5, "message", True, "XStat")]),
    ("XStatMetadata", [("name", 2, "string", False, None)]),
)
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


@functools.cache
def _xspace_type():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    types = {"message": F.TYPE_MESSAGE, "string": F.TYPE_STRING,
             "int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64}
    proto = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def add_fields(msg, fields):
        for name, number, kind, repeated, ref in fields:
            f = msg.field.add(
                name=name, number=number, type=types[kind],
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = f".bench_xplane.{ref}"

    for name, fields in _SCHEMA:
        msg = proto.message_type.add(name=name)
        add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                sub = msg.nested_type.add(name=entry)
                add_fields(sub, [("key", 1, "int64", False, None),
                                 ("value", 2, "message", False, value)])
                sub.options.map_entry = True
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def decode(data: bytes):
    """The XSpace message of a serialized ``.xplane.pb``."""
    return _xspace_type().FromString(data)


def scope_of(tf_op: str) -> str:
    """The innermost program scope on an operation's ``tf_op`` path."""
    path = tf_op.split("/")[:-1]
    for part in reversed(path):
        if part in SCOPES:
            return part
    return UNSCOPED


def _stat_str(stat, names: dict) -> str:
    return stat.str_value or (names.get(stat.ref_value, "")
                              if stat.ref_value else "")


def tf_ops(plane) -> dict[int, str]:
    """The operation path of each event metadata of a plane, by metadata
    id: its ``tf_op`` stat without the trailing ``:<type>``."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    key = next((k for k, v in names.items() if v == "tf_op"), None)
    out = {}
    for mid, meta in plane.event_metadata.items():
        op = next((_stat_str(s, names) for s in meta.stats
                   if s.metadata_id == key), "")
        path, sep, kind = op.rpartition(":")
        out[mid] = path if sep and "/" not in kind else op
    return out


def _events(line):
    """``(metadata id, start ns, end ns)`` of each event of a line."""
    base = line.timestamp_ns
    for e in line.events:
        start = base + e.offset_ps / 1e3
        yield e.metadata_id, start, start + e.duration_ps / 1e3


@dataclass
class Scoped:
    """One traced window of chip 0, by the program's own names."""
    lo: float                           # window, ns
    hi: float
    scope_s: dict[str, float]           # device seconds per innermost scope
    busy: list[tuple[float, float]]     # busy union, clipped
    main: list[tr.Event]                # program spans on the main line

    @property
    def scoped(self) -> bool:
        """Whether the program named any of its kernels."""
        return any(k != UNSCOPED for k in self.scope_s)

    def spans(self, name: str) -> list[tr.Event]:
        """Main-line spans called ``name`` that lie inside the window."""
        return [e for e in self.main if e.name == name
                and e.start >= self.lo and e.end <= self.hi]

    def idle_within(self, name: str) -> float:
        """Seconds chip 0 stood idle inside the main-line spans called
        ``name``."""
        return 1e-9 * sum(tr.length(tr.gaps(self.busy, e.start, e.end))
                          for e in self.spans(name))

    def idle_split(self, within: str = "iteration") -> dict[str, float]:
        """Chip 0's idle seconds inside the main-line spans called
        ``within``, by the main thread's innermost span at each instant;
        the parts sum to the whole."""
        pieces = flatten(self.main)
        starts = [p[0] for p in pieces]
        out: dict[str, float] = defaultdict(float)
        for span in self.spans(within):
            for s, e in tr.gaps(self.busy, span.start, span.end):
                i = max(bisect.bisect_right(starts, s) - 1, 0)
                while s < e:
                    if i == len(pieces) or pieces[i][0] >= e:
                        out[tr.NO_SPAN] += (e - s) * 1e-9
                        break
                    ps, pe, name = pieces[i]
                    i += 1
                    if pe <= s:
                        continue
                    if ps > s:
                        out[tr.NO_SPAN] += (ps - s) * 1e-9
                        s = ps
                    hi = min(e, pe)
                    out[name] += (hi - s) * 1e-9
                    s = hi
        return dict(out)


def flatten(spans: list[tr.Event]) -> list[tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of one thread's nested
    spans, each named by the innermost span open in it."""
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []     # (end, name), innermost last
    t = float("-inf")

    def close_until(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for ev in sorted(spans, key=lambda e: (e.start, -e.end)):
        close_until(ev.start)
        if stack and ev.start > t:
            pieces.append((t, ev.start, stack[-1][1]))
        t = max(t, ev.start)
        stack.append((min(ev.end, stack[-1][0]) if stack else ev.end,
                      ev.name))
    close_until(float("inf"))
    return pieces


def from_xspace(xspace, chip: int = 0) -> Scoped:
    """Reduce an XSpace over its ``bench_window`` for one chip."""
    device = f"/device:TPU:{chip}"
    ops_raw: list[tuple[float, float, str]] = []
    main: list[tr.Event] = []
    window = None
    for plane in xspace.planes:
        if plane.name == device:
            ops_of = tf_ops(plane)
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops_raw.extend((s, e, ops_of.get(m, ""))
                                   for m, s, e in _events(line))
        elif plane.name.startswith("/host:"):
            names = {k: v.name for k, v in plane.event_metadata.items()}
            for line in plane.lines:
                evs = [(names.get(m, ""), s, e)
                       for m, s, e in _events(line)]
                wins = [(s, e) for n, s, e in evs if n == tr.WINDOW]
                if wins:
                    window = wins
                    main = [tr.Event(n, s, e) for n, s, e in evs
                            if n in MAIN_SPANS]
    if window is None or len(window) != 1:
        raise ValueError(f"expected one {tr.WINDOW} span on one host line")
    lo, hi = window[0]
    ops = [(max(s, lo), min(e, hi), op) for s, e, op in ops_raw
           if e > lo and s < hi]
    if not ops:
        raise ValueError(f"no device operations traced on chip {chip}")
    scope_s: dict[str, float] = defaultdict(float)
    for s, e, op in ops:
        scope_s[scope_of(op)] += (e - s) * 1e-9
    return Scoped(lo=lo, hi=hi, scope_s=dict(scope_s),
                  busy=tr.merge((s, e) for s, e, _ in ops),
                  main=sorted(main, key=lambda ev: ev.start))


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int, size: int) -> Scoped:
    with open(path, "rb") as f:
        return from_xspace(decode(f.read()))


def for_run(run) -> Scoped | None:
    """The scoped reading of a traced run's profile, which the harness
    leaves under ``.bench_trace`` in the checkout that holds this
    module; None for an untraced run."""
    if run.trace is None:
        return None
    path = trace_file()
    st = os.stat(path)
    return _read(path, st.st_mtime_ns, st.st_size)


def trace_file() -> str:
    """The one ``.xplane.pb`` that the harness's traced run left under
    ``.bench_trace`` in the checkout."""
    trace_dir = os.path.join(ROOT, ".bench_trace")
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def iterations(run) -> int:
    """``iteration`` spans inside the traced window."""
    return len(run.trace.spans.get("iteration", [])) if run.trace else 0


def scope_per_iteration(run, scope: str) -> float | None:
    """Chip 0's device seconds in ``scope`` per traced iteration."""
    s = for_run(run)
    n = iterations(run)
    if s is None or not s.scoped or not n:
        return None
    return s.scope_s.get(scope, 0.0) / n


def scope_coverage(run) -> float | None:
    """The share of chip 0's compute time in a named scope, in percent."""
    s = for_run(run)
    if s is None or not s.scoped or run.trace.compute_s <= 0:
        return None
    named = sum(v for k, v in s.scope_s.items() if k != UNSCOPED)
    return 100.0 * named / run.trace.compute_s


def idle_per_iteration(run, span: str) -> float | None:
    """Chip 0's idle seconds inside main-line ``span`` spans per traced
    iteration; None where the program never recorded such a span, in
    the window or before it."""
    s = for_run(run)
    n = iterations(run)
    if s is None or not n or not (s.spans(span) or program_spans(span)):
        return None
    return s.idle_within(span) / n


def program_spans(name: str) -> list:
    """The ``repro.obs`` spans called ``name`` that the program recorded
    in this process, set-up included (the harness turns the tracer on
    before it compiles the plan)."""
    from repro import obs

    t = obs.tracer()
    return t.spans(name) if t is not None else []


def split_at_window(run, name: str) -> tuple[list, list] | None:
    """The program's ``name`` spans that ended before the window and
    those that started inside it; None where it recorded none at all."""
    spans = program_spans(name)
    if not spans:
        return None
    t0 = run.window_start * 1e9
    return ([e for e in spans if e.end_ns <= t0],
            [e for e in spans if e.start_ns >= t0])
