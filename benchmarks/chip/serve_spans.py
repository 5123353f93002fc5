"""The engine's own ``serve.*`` spans in a profiler trace, and device idle by them.

The program mirrors its engine spans into the profiler (``repro.obs.trace``):
``serve.plan`` with its stages ``serve.plan.pick``, ``serve.plan.encrypt``
and ``serve.plan.dispatch``; ``serve.gemm``; ``serve.complete`` with its
``serve.complete.fetch``.  The plan, gemm and complete carry their batch's
``bid``.  This reads them from the same trace as ``trace_reduce`` and puts
device 0's idle time beside them.

Output (`Spans`), window-relative, against the ``bench.window`` annotation:

* ``events``: every ``serve.*`` host event that overlaps the window, as
  ``(name, start_s, dur_s, bid)`` in start order.  ``bid`` is the event's
  own, else that of the innermost event around it that has one (a plan's
  or a complete's stages), else None;
* ``idle``: device 0's idle intervals in the window, ``(start_s, dur_s)``
  (the complement of the union of its ``XLA Ops``, as ``trace_reduce``
  computes busy time).

A program that mirrors no spans leaves ``events`` empty: `of_run` then
gives None, and so does every reader that uses it.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
from collections import defaultdict
from pathlib import Path

from trace_reduce import (DEVICE_PLANE, HOST_PLANE, OPS_LINE, WINDOW,
                          _clip, _union)

PREFIX = "serve."
#: The stages in which the engine works on a batch; outside all of them it
#: waits for arrivals or the batcher's deadline.
ENGINE_STAGES = ("serve.plan", "serve.gemm", "serve.complete")
#: Where ``run.py`` has the profiler write a ``--trace 1`` window.
TRACE_DIR = Path(__file__).resolve().parent / "out" / "trace"


@dataclasses.dataclass
class Spans:
    window_s: float
    events: list          # (name, start_s, dur_s, bid)
    idle: list            # (start_s, dur_s), device 0

    def inside(self, name: str) -> list:
        """The events called ``name`` that lie wholly in the window."""
        return [e for e in self.events if e[0] == name
                and e[1] >= 0 and e[1] + e[2] <= self.window_s]

    def mean_ms(self, name: str) -> float | None:
        """Mean duration of the ``name`` events in the window (one a batch)."""
        durs = [e[2] for e in self.inside(name)]
        return 1e3 * sum(durs) / len(durs) if durs else None

    def inflight_ms(self) -> float | None:
        """Mean, over batches planned and retired in the window, of the
        ``serve.gemm`` start − the ``serve.plan`` end."""
        plan_end = {e[3]: e[1] + e[2] for e in self.inside("serve.plan")}
        waits = [e[1] - plan_end[e[3]] for e in self.inside("serve.gemm")
                 if e[3] in plan_end]
        return 1e3 * sum(waits) / len(waits) if waits else None

    def idle_s(self, names=None) -> float:
        """Seconds device 0 was idle while the host was inside one of the
        ``names`` events (all idle time when ``names`` is None)."""
        if names is None:
            return sum(d for _, d in self.idle)
        host = _union([(e[1], e[1] + e[2]) for e in self.events
                       if e[0] in names])
        return _overlap([(a, a + d) for a, d in self.idle], host)


def _overlap(xs: list, ys: list) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _inherit_bids(events: list) -> list:
    """Give each event without a ``bid`` that of the innermost event around
    it on its thread that has one.  ``events``: (start, end, name, bid,
    thread), sorted by start then longest first."""
    out, open_by_thread = [], defaultdict(list)
    for a, b, name, bid, thread in events:
        stack = open_by_thread[thread]
        while stack and stack[-1][0] <= a:
            stack.pop()
        if bid is None:
            bid = next((s[1] for s in reversed(stack) if s[1] is not None),
                       None)
        stack.append((b, bid))
        out.append((a, b, name, bid))
    return out


def reduce_bytes(data: bytes) -> Spans | None:
    """Reduce a serialized trace; None when it holds no window or device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(data)
    window, host, dev0 = None, [], None
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name == WINDOW and window is None:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                    elif e.name.startswith(PREFIX):
                        host.append((e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9,
                                     e.name, dict(e.stats).get("bid"),
                                     thread))
        elif plane.name.startswith(DEVICE_PLANE) and dev0 is None:
            dev0 = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for line in plane.lines if line.name == OPS_LINE
                    for e in line.events]
    if window is None or not dev0:
        return None
    lo, hi = window
    idle, t = [], lo
    for a, b in _union([c for a, b in dev0 if (c := _clip(a, b, lo, hi))]
                       ) + [(hi, hi)]:
        if a > t:
            idle.append((t - lo, a - t))
        t = max(t, b)
    host.sort(key=lambda e: (e[0], -e[1]))
    events = [(name, a - lo, b - a, bid)
              for a, b, name, bid in _inherit_bids(host)
              if b > lo and a < hi]
    return Spans(window_s=hi - lo, events=events, idle=idle)


def reduce_file(path) -> Spans | None:
    """Reduce one ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        return reduce_bytes(f.read())


def of_run(run) -> Spans | None:
    """The spans of a ``--trace 1`` run's window, reduced once per run;
    None without a trace or where the program mirrored no ``serve.*``
    span."""
    if run.trace is None:
        return None
    if not hasattr(run, "serve_spans"):
        found = sorted(glob.glob(os.path.join(str(TRACE_DIR), "**",
                                              "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        run.serve_spans = reduce_file(found[-1]) if found else None
    s = run.serve_spans
    return s if s is not None and s.events else None
