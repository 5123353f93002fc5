"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's numbers.

Input: the trace of one window, with the benchmark's own host annotations
(``bench.window`` around the whole window; ``bench.submit``, ``bench.tick``,
``bench.plan``, ``bench.complete``, ``bench.commit`` inside it).

Output (`Reduced`), all clipped to the ``bench.window`` annotation:

* ``busy_s``: length of the union of the intervals in which an operation
  ran on the device (the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane), averaged over the devices; ``window_s``: the window's length;
* ``op_s``: device seconds per program (the ``XLA Modules`` line, names
  without their ``(fingerprint)``), and ``modules``: every program run
  wholly inside the window as ``(name, start_s, dur_s)``, window-relative,
  in device order;
* ``gaps``: every idle interval of device 0 with the host annotation that
  covers most of it (the innermost ``bench.*`` span; "none" where the host
  was in no annotated stage).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: dict            # program name → device seconds (device 0)
    modules: list         # (name, start_s, dur_s), device 0, window-relative
    gaps: list            # (start_s, dur_s, host stage), device 0

    def breakdown(self, top: int = 10) -> dict:
        """The ``breakdown`` of the result line: longest ops and gaps."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[w, d] for _, d, w in gaps]}

    def gap_s_by_stage(self) -> dict:
        out: dict = defaultdict(float)
        for _, d, w in self.gaps:
            out[w] += d
        return dict(out)


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce_file(path: str) -> Reduced | None:
    """Reduce one ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        return reduce_bytes(f.read())


def reduce_bytes(data: bytes) -> Reduced | None:
    """Reduce a serialized trace; None when it holds no window or device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(data)
    host_spans: list[tuple[float, float, str]] = []
    devices: list[list[tuple[float, float, str]]] = []
    programs: list[tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host_spans.append((e.start_ns * 1e-9,
                                           (e.start_ns + e.duration_ns) * 1e-9,
                                           e.name))
        elif plane.name.startswith(DEVICE_PLANE):
            evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                    e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if not devices:
                programs = [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             re.sub(r"\(\d+\)$", "", e.name))
                            for line in plane.lines
                            if line.name == MODULES_LINE
                            for e in line.events]
            devices.append(evs)
    win = [s for s in host_spans if s[2] == WINDOW]
    if not win or not devices or not any(devices):
        return None
    lo, hi = win[0][0], win[0][1]
    busy = []
    for evs in devices:
        iv = [c for a, b, _ in evs if (c := _clip(a, b, lo, hi))]
        busy.append(sum(b - a for a, b in _union(iv)))
    dev0 = sorted(devices[0])
    op_s: dict = defaultdict(float)
    modules = []
    for a, b, name in sorted(programs):
        c = _clip(a, b, lo, hi)
        if c:
            op_s[name] += c[1] - c[0]
            if c == (a, b):
                modules.append((name, a - lo, b - a))
    stages = sorted(s for s in host_spans if s[2] != WINDOW)
    starts = [s[0] for s in stages]
    longest = max((s[1] - s[0] for s in stages), default=0.0)
    gaps = []
    t = lo
    for a, b in _union([c for a, b, _ in dev0
                        if (c := _clip(a, b, lo, hi))]) + [(hi, hi)]:
        if a > t:
            near = stages[bisect.bisect_left(starts, t - longest):
                          bisect.bisect_left(starts, a)]
            gaps.append((t - lo, a - t, _stage(near, t, a)))
        t = max(t, b)
    return Reduced(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                   op_s=dict(op_s), modules=modules, gaps=gaps)


def _stage(stages, a: float, b: float) -> str:
    """The innermost host stage covering most of [a, b]."""
    best, best_cover, best_len = "none", 0.0, float("inf")
    for s0, s1, name in stages:
        cover = min(b, s1) - max(a, s0)
        if cover <= 0:
            continue
        if cover > best_cover + 1e-12 or (
                abs(cover - best_cover) <= 1e-12 and s1 - s0 < best_len):
            best, best_cover, best_len = name, cover, s1 - s0
    return best


def reduce_dir(log_dir) -> Reduced | None:
    """Reduce the newest trace the profiler wrote under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return reduce_file(found[-1]) if found else None
