"""Profiler trace capture and its reduction to device busy time,
per-program device time and labelled idle gaps.

The reduction reads a flat list of events, each a dict with ``plane``,
``line``, ``name``, ``start_ns`` and ``dur_ns``, so that a small recorded
trace can be checked without the chip (``tests/test_trace.py``).
:func:`load_xplane` makes that list from the ``.xplane.pb`` file that
``jax.profiler`` writes; device and host events share its clock.

- Device planes are those named ``/device:TPU:<n>``.  Their ``XLA Ops``
  line gives the busy time (the union of op intervals); their
  ``XLA Modules`` line gives one event per program execution, named
  after the jitted function (``jit_decode_step(7)`` reads as
  ``decode_step``).
- Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
  events on the host plane; an idle gap is labelled with the innermost
  span open at its midpoint, or ``outside`` where none is.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def program_name(event_name: str) -> str:
    """``jit_decode_step(7)`` → ``decode_step``."""
    name = re.sub(r"\(.*\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def load_xplane(path: str, span_prefixes: Sequence[str]) -> List[dict]:
    """The device op and program events, and the host spans whose names
    start with one of ``span_prefixes``, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not device and not e.name.startswith(tuple(span_prefixes)):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": e.name, "start_ns": int(e.start_ns),
                            "dur_ns": int(e.duration_ns)})
    return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(files)}")
    return files[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class Reduced:
    """What the metric readers get from one traced window."""
    window_s: float
    busy_s: float                      # mean over the device planes
    # per program: execution events (start_ns, dur_ns), in start order
    programs: Dict[str, List[Tuple[int, int]]] = field(default_factory=dict)
    # idle gaps on device 0: (label, start_ns, length_ns)
    gaps: List[Tuple[str, int, int]] = field(default_factory=list)

    def device_ops(self, top: int = 10) -> List[list]:
        rows = [[n, sum(d for _, d in ev) * 1e-9]
                for n, ev in self.programs.items()]
        return sorted(rows, key=lambda r: -r[1])[:top]

    def idle_gaps(self, top: int = 10) -> List[list]:
        by: Dict[str, int] = {}
        for label, _, length in self.gaps:
            by[label] = by.get(label, 0) + length
        rows = [[k, v * 1e-9] for k, v in by.items()]
        return sorted(rows, key=lambda r: -r[1])[:top]


def reduce_events(events: List[dict], t0_ns: int, t1_ns: int) -> Reduced:
    """Reduce a trace of the window ``[t0_ns, t1_ns)`` (trace clock).
    Busy time clips the device's ops to the window; every program
    execution in the trace counts, since the device clock may sit a
    fraction of a millisecond off the host spans that mark the window."""
    def clip(e):
        s = max(e["start_ns"], t0_ns)
        t = min(e["start_ns"] + e["dur_ns"], t1_ns)
        return (s, t) if t > s else None

    planes = sorted({e["plane"] for e in events
                     if DEVICE_PLANE.match(e["plane"])})
    if not planes:
        raise RuntimeError("the trace holds no TPU device plane")
    busy_ns = 0
    gaps: List[Tuple[str, int, int]] = []
    programs: Dict[str, List[Tuple[int, int]]] = {}
    spans = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
                   for e in events if not DEVICE_PLANE.match(e["plane"]))
    starts = [s[0] for s in spans]
    for i, plane in enumerate(planes):
        ops = [iv for e in events
               if e["plane"] == plane and e["line"] == OPS_LINE
               for iv in [clip(e)] if iv]
        mods = [e for e in events
                if e["plane"] == plane and e["line"] == MODULES_LINE]
        busy = _union(ops or [iv for e in mods for iv in [clip(e)] if iv])
        busy_ns += sum(e - s for s, e in busy)
        if i:
            continue
        for e in mods:     # the trace holds the window's programs only
            programs.setdefault(program_name(e["name"]), []).append(
                (e["start_ns"], e["dur_ns"]))
        edges = [t0_ns] + [x for iv in busy for x in iv] + [t1_ns]
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                gaps.append((_label(spans, starts, (s + t) // 2), s, t - s))
    return Reduced(window_s=(t1_ns - t0_ns) * 1e-9,
                   busy_s=busy_ns * 1e-9 / len(planes),
                   programs=programs, gaps=gaps)


def _label(spans, starts, t: int) -> str:
    """The innermost (latest-starting) span open at ``t``."""
    i = bisect.bisect_right(starts, t)
    while i:
        i -= 1
        if spans[i][1] > t:
            return spans[i][2]
    return "outside"


class Capture:
    """A context manager that traces its body into ``log_dir``, with a
    host span named ``window_span`` around it; :meth:`reduce` then
    reduces the events inside that span."""

    def __init__(self, log_dir: str, window_span: str):
        self.log_dir = log_dir
        self.window_span = window_span

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(self.window_span)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._span.__exit__(*exc)
        jax.profiler.stop_trace()

    def reduce(self, span_prefixes: Sequence[str]) -> Reduced:
        """The window's reduction; host spans are those whose names start
        with one of ``span_prefixes``."""
        events = load_xplane(find_xplane(self.log_dir),
                             tuple(span_prefixes) + (self.window_span,))
        win = [e for e in events if e["name"] == self.window_span]
        if len(win) != 1:
            raise RuntimeError(f"{len(win)} '{self.window_span}' spans in "
                               "the trace, expected one")
        t0 = win[0]["start_ns"]
        return reduce_events([e for e in events if e is not win[0]],
                             t0, t0 + win[0]["dur_ns"])
