"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle
share, kernel device time and the ``breakdown`` of a traced run.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, named by their HLO instruction
  (``%paged_decode_attention.1 = ...`` -> ``paged_decode_attention.1``)
  and attributed to the program (``XLA Modules`` line) running them.
* Busy time is the union of those events' intervals inside the window,
  averaged over the chips; the idle share is 1 - busy / window.
* The window is the host span named ``bench.window`` (a
  ``jax.profiler.TraceAnnotation`` around the measured serving loop).
  The trace puts device events on the host's clock; on a v5e they read
  about a millisecond early against host spans, nothing against a window
  of seconds.
* An idle gap is a stretch of the window in which no operation runs; it
  is attributed to the innermost host event of the window's thread that
  covers the gap's midpoint: what the host was doing meanwhile.

Only JAX is needed (``jax.profiler.ProfileData``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import math
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]           # (start_ns, end_ns)


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: float
    end: float
    chip: int


@dataclasses.dataclass
class Reduced:
    window: Interval
    ops: List[Op]                        # device ops inside the window
    host: List[Tuple[str, float, float]]  # window thread's host events
    chips: int
    _starts: Optional[List[float]] = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Union of busy intervals, averaged over the chips."""
        tot = 0.0
        for c in range(self.chips):
            tot += union([(o.start, o.end) for o in self.ops if o.chip == c],
                         *self.window)
        return tot * 1e-9 / max(1, self.chips)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def kernel_s(self, pattern: str) -> float:
        """Summed device time of ops whose name or module matches."""
        rx = re.compile(pattern)
        return 1e-9 * sum(o.end - o.start for o in self.ops
                          if rx.search(o.name) or rx.search(o.module))

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        by_op: Dict[str, float] = collections.Counter()
        for o in self.ops:
            by_op[f"{o.module}/{o.name}"] += (o.end - o.start) * 1e-9
        gaps_by: Dict[str, float] = collections.Counter()
        for lo, hi in gaps([(o.start, o.end) for o in self.ops if
                            o.chip == 0], *self.window):
            gaps_by[self.host_at((lo + hi) / 2)] += (hi - lo) * 1e-9
        return {"device_ops": [[k, v] for k, v in
                               sorted(by_op.items(), key=lambda kv: -kv[1])
                               [:top]],
                "idle_gaps": [[k, v] for k, v in
                              sorted(gaps_by.items(), key=lambda kv: -kv[1])
                              [:top]]}

    def host_at(self, t: float) -> str:
        """The innermost host event covering ``t``: events of one thread
        nest, so it is the covering event that started last."""
        if self._starts is None:
            self.host.sort(key=lambda ev: (ev[1], -ev[2]))
            self._starts = [ev[1] for ev in self.host]
        i = bisect.bisect_right(self._starts, t) - 1
        while i >= 0:
            name, _, end = self.host[i]
            if end >= t and name != WINDOW:
                return name
            i -= 1
        return "(no host span)"


def union(iv: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``iv`` clipped to [lo, hi]."""
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in iv):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def gaps(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(iv):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _op_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(path: str, window: Optional[str] = WINDOW) -> Reduced:
    """Reduce the trace at ``path`` over the host span named ``window``;
    ``window=None`` takes the stretch from the first device operation to
    the end of the last."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    if window is None:
        ops = _device_ops(pd, (-math.inf, math.inf))
        lo = min(o.start for o in ops)
        hi = max(o.end for o in ops)
        return Reduced(window=(lo, hi), ops=ops, host=[],
                       chips=1 + max(o.chip for o in ops))
    win, host = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            w = [e for e in evs if e[0] == window]
            if w:
                win = (w[0][1], w[0][2])
                host = evs
    if win is None:
        raise ValueError(f"no host span {window!r} in {path}")
    chips = sorted(int(m.group(1)) for m in (
        _DEVICE.match(p.name) for p in pd.planes) if m)
    return Reduced(window=win, ops=_device_ops(pd, win), host=host,
                   chips=len(chips))


def _device_ops(pd, win: Interval) -> List[Op]:
    """Device operations overlapping ``win``, clipped to it; chips are
    numbered 0.. in plane order."""
    ops = []
    planes = [p for p in pd.planes if _DEVICE.match(p.name)]
    planes.sort(key=lambda p: int(_DEVICE.match(p.name).group(1)))
    for chip, plane in enumerate(planes):
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _module_name(e.name))
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        for e in lines.get("XLA Ops", []):
            s, t = e.start_ns, e.start_ns + e.duration_ns
            if t <= win[0] or s >= win[1]:
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and mods[i][1] >= s else ""
            ops.append(Op(name=_op_name(e.name), module=module,
                          start=max(s, win[0]), end=min(t, win[1]),
                          chip=chip))
    return ops
