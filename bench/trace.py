"""Reduce a profiler trace (``.xplane.pb``) to device busy, kernel and
collective time, and idle gaps labelled by what the host was doing.

The reduction belongs to the benchmark, so every PR computes these numbers
the same way:

* the window is the host span the harness opens around the timed work;
* a device's busy time is the union of its operation intervals (events on
  its ``XLA Ops`` line) inside the window, so overlapping events count once;
* the time of a kernel or a collective is the sum of the in-window durations
  of the operations whose name matches a pattern;
* an idle gap is a stretch of the window in which a device runs nothing; it
  is labelled by the innermost benchmark span open on the host at its middle.

Shares are averaged over the devices traced. ``PEAKS`` holds the published
peaks of each chip this benchmark runs on, keyed by ``device_kind``; a device
that is not in it is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Google Cloud documentation, "TPU v5e": per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e",
    },
}

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


Interval = Tuple[int, int]  # [start_ns, end_ns)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of half-open intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: int, e: int, lo: int, hi: int) -> int:
    return max(0, min(e, hi) - max(s, lo))


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Tuple[str, int, int]]      # (op name, start, end)
    modules: List[Tuple[str, int, int]]  # (jitted program, start, end)


@dataclasses.dataclass
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class TraceSummary:
    window: Interval
    devices: List[DeviceTrace]
    spans: List[Span]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy(self, dev: DeviceTrace) -> List[Interval]:
        lo, hi = self.window
        return [(max(s, lo), min(e, hi))
                for s, e in union((s, e) for _, s, e in dev.ops)
                if e > lo and s < hi]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self._busy(d))
                   for d in self.devices) * 1e-9 / len(self.devices)

    def idle_share(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """In-window seconds of the operations whose name matches
        ``pattern`` (a regular expression), averaged over the devices."""
        if not self.devices:
            return 0.0
        rx = re.compile(pattern)
        lo, hi = self.window
        total = 0
        for d in self.devices:
            total += sum(e - s for s, e in union(
                (max(s, lo), min(e, hi)) for n, s, e in d.ops
                if rx.search(n) and e > lo and s < hi))
        return total * 1e-9 / len(self.devices)

    def op_share(self, pattern: str) -> Optional[float]:
        """``op_seconds(pattern)`` over the window; None where no operation
        matched, so that a reader reports nothing rather than 0."""
        secs = self.op_seconds(pattern)
        if secs <= 0 or self.window_s <= 0:
            return None
        return secs / self.window_s

    def top_programs(self, k: int = 10, ops: bool = False) -> List[Tuple[str, float]]:
        """The jitted programs (or, with ``ops`` or without a modules line,
        the operations) that ran longest in the window, seconds averaged over
        devices."""
        lo, hi = self.window
        acc: Dict[str, int] = {}
        for d in self.devices:
            for n, s, e in (d.ops if ops else d.modules or d.ops):
                acc[n] = acc.get(n, 0) + _clip(s, e, lo, hi)
        n_dev = max(1, len(self.devices))
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [(n, t * 1e-9 / n_dev) for n, t in ranked if t > 0]

    def _label(self, t: int) -> str:
        """The innermost benchmark span open at ``t`` (the latest to open)."""
        best = None
        for sp in self.spans:
            if sp.start <= t < sp.end and sp.name != WINDOW_SPAN:
                if best is None or sp.start >= best.start:
                    best = sp
        return best.name if best is not None else "outside any span"

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds per host span label, averaged over devices."""
        lo, hi = self.window
        acc: Dict[str, int] = {}
        for d in self.devices:
            cur = lo
            for s, e in self._busy(d) + [(hi, hi)]:
                if s > cur:
                    lab = self._label((cur + s) // 2)
                    acc[lab] = acc.get(lab, 0) + (s - cur)
                cur = max(cur, e)
        n_dev = max(1, len(self.devices))
        return {k: v * 1e-9 / n_dev for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])}


def _events(line) -> List[Tuple[str, int, int]]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def summarize(profile, span_names: Sequence[str]) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` (or anything with its shape)."""
    names = set(span_names) | {WINDOW_SPAN}
    devices: List[DeviceTrace] = []
    spans: List[Span] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            devices.append(DeviceTrace(plane.name, ops or modules, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(n, s, e) for n, s, e in _events(line)
                             if n in names)
    windows = [sp for sp in spans if sp.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w = max(windows, key=lambda sp: sp.end - sp.start)
    return TraceSummary((w.start, w.end), devices, spans)


def load(trace_dir: str, span_names: Sequence[str]) -> TraceSummary:
    """Summarize the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax._src.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(ProfileData.from_file(max(found, key=os.path.getmtime)),
                     span_names)
