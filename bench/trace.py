"""Profiler trace capture and its reduction to numbers.

A traced run wraps its window in ``jax.profiler`` and in a host
annotation named ``bench.window``; the harness's calls into the
program carry annotations named ``bench.<what>``.  The reduction reads
the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and keeps, inside
the window:

- device events: the ``XLA Modules`` (one per program execution) and
  ``XLA Ops`` lines of each ``/device:TPU:<n>`` plane;
- host annotations: events named ``bench.*`` on the host plane.

Busy time is the union of the device's module intervals, the idle
share is one minus busy over the window, and each idle gap is labelled
with the innermost ``bench.*`` annotation that covers its middle.
"""
from __future__ import annotations

import glob
import shutil
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, \
    Tuple

from bench.common import log

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, end_ns)


# ---------------------------------------------------------------------------
# pure reduction
# ---------------------------------------------------------------------------
def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], w0: float, w1: float
         ) -> List[Interval]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def busy_ns(intervals: Iterable[Interval], w0: float, w1: float) -> float:
    """Length of the union of ``intervals`` inside ``[w0, w1]``."""
    return sum(e - s for s, e in merge(clip(intervals, w0, w1)))


def gaps(intervals: Iterable[Interval], w0: float, w1: float
         ) -> List[Interval]:
    """The stretches of ``[w0, w1]`` that no interval covers."""
    out, t = [], w0
    for s, e in merge(clip(intervals, w0, w1)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


def time_by_name(events: Iterable[Event],
                 match: Callable[[str], bool]) -> float:
    """Summed duration (ns) of the events whose name matches."""
    return sum(e - s for n, s, e in events if match(n))


def top_names(events: Iterable[Event], n: int = 10
              ) -> List[Tuple[str, float]]:
    """The ``n`` names with the most summed duration, in seconds."""
    tot: Dict[str, float] = {}
    for name, s, e in events:
        tot[name] = tot.get(name, 0.0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in best]


def label(t: float, spans: Sequence[Event]) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best, width = "untraced", float("inf")
    for name, s, e in spans:
        if s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def top_gaps(gap_list: Sequence[Interval], spans: Sequence[Event],
             n: int = 10) -> List[Tuple[str, float]]:
    """Idle time summed by the host span it fell in, longest first."""
    tot: Dict[str, float] = {}
    for s, e in gap_list:
        k = label((s + e) / 2, spans)
        tot[k] = tot.get(k, 0.0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in best]


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------
@dataclass
class Reduced:
    window: Interval
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Device-busy seconds averaged over the device planes."""
        if not self.modules:
            return 0.0
        w0, w1 = self.window
        per = [busy_ns([(s, e) for _, s, e in evs], w0, w1)
               for evs in self.modules.values()]
        return sum(per) / len(per) / 1e9

    def idle_share(self) -> Optional[float]:
        if not self.modules or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def all_modules(self) -> List[Event]:
        return [ev for evs in self.modules.values() for ev in evs]

    def all_ops(self) -> List[Event]:
        return [ev for evs in self.ops.values() for ev in evs]

    def breakdown(self) -> dict:
        w0, w1 = self.window
        plane = sorted(self.modules)[0] if self.modules else None
        idle = gaps([(s, e) for _, s, e in self.modules[plane]], w0, w1) \
            if plane else []
        return {"device_ops": [[k, v] for k, v in
                               top_names(self.all_ops(), 10)],
                "idle_gaps": [[k, v] for k, v in
                              top_gaps(idle, self.host, 10)]}


def _events(line) -> List[Event]:
    """(name, start, end) of a line's events; an operation's name is
    the first word of its HLO text (``%fusion.12``)."""
    return [(ev.name.split(" ", 1)[0], float(ev.start_ns),
             float(ev.start_ns + ev.duration_ns)) for ev in line.events]


def read_xplane(path: str, device_prefix: str = "/device:TPU:",
                module_line: str = "XLA Modules", op_line: str = "XLA Ops",
                window_name: str = "bench.window") -> Reduced:
    """Device modules/ops and ``bench.*`` host spans of one trace,
    clipped to the ``bench.window`` annotation.  A device plane's lines
    whose names start with ``module_line`` hold its program executions,
    those starting with ``op_line`` its operations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            log(f"trace: {plane.name} lines "
                f"{[ln.name for ln in plane.lines]}")
            for line in plane.lines:
                if line.name.startswith(module_line):
                    modules.setdefault(plane.name, []).extend(
                        _events(line))
                elif line.name.startswith(op_line):
                    ops.setdefault(plane.name, []).extend(_events(line))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith("bench."))
    wins = [ev for ev in host if ev[0] == window_name]
    if not wins:
        raise RuntimeError(f"no {window_name} annotation in {path}")
    w0, w1 = wins[0][1], wins[0][2]
    red = Reduced(window=(w0, w1))
    for k, evs in modules.items():
        red.modules[k] = [ev for ev in evs if ev[2] > w0 and ev[1] < w1]
    for k, evs in ops.items():
        red.ops[k] = [ev for ev in evs if ev[2] > w0 and ev[1] < w1]
    red.host = [ev for ev in host if ev[2] > w0 and ev[1] < w1]
    return red


class Profiler:
    """Start/stop the JAX profiler into a fixed directory and reduce
    what it wrote; the raw trace is deleted once it is read."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(self.dir))

    def stop(self, **kw) -> Reduced:
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"the profiler wrote no trace in "
                               f"{self.dir}")
        try:
            return read_xplane(found[0], **kw)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
