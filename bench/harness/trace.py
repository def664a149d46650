"""From a profiler trace to numbers.

``capture`` records a trace of the window; ``load_events`` turns the
``.xplane.pb`` the profiler writes into a flat list of events
(``Event``); the functions after it reduce that list. Only the
reductions are used by the metric readers, and the tests check them on
a small recorded trace (``tests/data/``), so every change computes a
metric the same way.

Event kinds:
  "op"      an operation on a device (an HLO op or a kernel), line
            "XLA Ops" of a ``/device:...`` plane
  "module"  one execution of a compiled program on a device, line
            "XLA Modules"
  "host"    anything on a ``/host:`` plane (Python, dispatch, the
            benchmark's window markers)
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_START = "bench.window_start"
WINDOW_END = "bench.window_end"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|send|recv", re.I)


@dataclasses.dataclass(frozen=True)
class Event:
    kind: str          # "op" | "module" | "host"
    device: str        # plane name
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _kind(plane: str, line: str) -> Optional[str]:
    if plane.startswith("/host:"):
        return "host"
    if plane.startswith("/device:") and line == "XLA Ops":
        return "op"
    if plane.startswith("/device:") and line == "XLA Modules":
        return "module"
    return None


def load_events(xplane_path: str) -> List[Event]:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            kind = _kind(plane.name, line.name)
            if kind is None:
                continue
            for e in line.events:
                out.append(Event(kind, plane.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str) -> List[Event]:
    """Events saved as a gzipped JSON list of ``Event`` fields (the
    recorded trace the tests read)."""
    with gzip.open(path, "rt") as f:
        return [Event(*row) for row in json.load(f)]


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------
def window(events: Sequence[Event]) -> Tuple[float, float]:
    """[start, end] of the traced window, from the host markers."""
    starts = [e.start_ns for e in events if e.name == WINDOW_START]
    ends = [e.start_ns for e in events if e.name == WINDOW_END]
    if not starts or not ends:
        raise ValueError("the trace holds no window markers")
    return min(starts), max(ends)


def devices(events: Sequence[Event]) -> List[str]:
    return sorted({e.device for e in events if e.kind == "op"})


def _clip(events: Iterable[Event], w: Tuple[float, float]
          ) -> List[Tuple[float, float]]:
    lo, hi = w
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_ns(events: Sequence[Event], device: str,
            w: Tuple[float, float]) -> float:
    """Length of the union of ``device``'s op intervals inside ``w``."""
    ops = (e for e in events if e.kind == "op" and e.device == device)
    return sum(t - s for s, t in union(_clip(ops, w)))


def mean_busy_s(events: Sequence[Event], w: Tuple[float, float]) -> float:
    devs = devices(events)
    if not devs:
        return 0.0
    return sum(busy_ns(events, d, w) for d in devs) / len(devs) / 1e9


def select(events: Sequence[Event], kind: str, pattern: str,
           w: Tuple[float, float]) -> List[Event]:
    """The ``kind`` events that start in ``w`` and whose name matches
    ``pattern`` (a regular expression, searched), whole."""
    rx = re.compile(pattern)
    lo, hi = w
    return [e for e in events if e.kind == kind and lo <= e.start_ns < hi
            and rx.search(e.name)]


def total_ns(events: Sequence[Event]) -> float:
    return sum(e.dur_ns for e in events)


def exposed_collective_ns(events: Sequence[Event], device: str,
                          w: Tuple[float, float]) -> float:
    """Time in ``w`` during which a collective op runs on ``device``
    and no other op does."""
    ops = [e for e in events if e.kind == "op" and e.device == device]
    coll = union(_clip((e for e in ops if COLLECTIVE.search(e.name)), w))
    other = union(_clip((e for e in ops if not COLLECTIVE.search(e.name)),
                        w))
    exposed, j = 0.0, 0
    for s, t in coll:
        covered = 0.0
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < t:
            covered += min(t, other[k][1]) - max(s, other[k][0])
            k += 1
        exposed += (t - s) - covered
    return exposed


_OPCODE = re.compile(r" = .*? ([a-z][\w-]*)\(")


def short_name(op_text: str) -> str:
    """``%fusion.12 fusion`` from the HLO text the trace prints."""
    m = _OPCODE.search(op_text)
    head = op_text.split(" = ", 1)[0]
    return f"{head} {m.group(1)}" if m else head


def leaf_ops(events: Sequence[Event]) -> List[Event]:
    """Device ops that contain no other op (a while loop's or a call's
    own event spans the ops inside it)."""
    out = []
    for dev in devices(events):
        ops = sorted((e for e in events if e.kind == "op" and e.device == dev),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        for a, b in zip(ops, ops[1:] + [None]):
            if b is None or not (b.start_ns < a.end_ns
                                 and b.end_ns <= a.end_ns):
                out.append(a)
    return out


def top_ops(events: Sequence[Event], w: Tuple[float, float], n: int = 10
            ) -> List[Tuple[str, float]]:
    """Leaf device ops by total time in ``w`` (seconds, averaged over
    devices), largest first, under their short names."""
    devs = devices(events) or [""]
    tot: Dict[str, float] = defaultdict(float)
    for e in leaf_ops(events):
        for s, t in _clip([e], w):
            tot[short_name(e.name)] += (t - s) / 1e9 / len(devs)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(events: Sequence[Event], w: Tuple[float, float],
              n: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps between device ops on the first device, each
    named by the host event that overlaps it most (the shortest such
    event on a tie): what the host was doing while the device idled."""
    devs = devices(events)
    if not devs:
        return []
    busy = union(_clip((e for e in events
                        if e.kind == "op" and e.device == devs[0]), w))
    gaps, prev = [], w[0]
    for s, t in busy + [(w[1], w[1])]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    host = [e for e in events if e.kind == "host"]
    out = []
    for s, t in gaps:
        best, key = "(no host event)", (0.0, 0.0)
        for e in host:
            ov = min(t, e.end_ns) - max(s, e.start_ns)
            if ov > 0 and (ov, -e.dur_ns) > key:
                best, key = e.name, (ov, -e.dur_ns)
        out.append((best, (t - s) / 1e9))
    return out


def capture_start(trace_dir: str) -> None:
    import jax
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(WINDOW_START):
        pass


def capture_stop() -> None:
    import jax
    with jax.profiler.TraceAnnotation(WINDOW_END):
        pass
    jax.profiler.stop_trace()
