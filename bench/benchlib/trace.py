"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's readings.

Read with ``jax.profiler.ProfileData``.  A TPU device plane is named
``/device:TPU:<i>``; its ``XLA Modules`` line holds one event per program
run (these never overlap) and its ``XLA Ops`` line one event per HLO
instruction run, named by the instruction's text (``%name = shape
op(...)``), where a ``while`` or a fusion encloses the events of its body.
Host planes (``/host:...``) hold the benchmark's own spans (``bench.*``,
from ``jax.profiler.TraceAnnotation``) and JAX's dispatch events.

* busy: the union of the device's module intervals inside the traced
  window, averaged over the chips read; idle is the rest of the window;
* kernel time: per instruction, its duration, and its self time (its
  duration less the events it encloses);
* idle gaps: the complement of the busy union inside the window, each
  named after the host spans it overlaps.

Device and host timestamps are on one clock to within about a
millisecond, so only gaps longer than that are attributed reliably.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.traced_window"
SPAN_PREFIX = "bench."
GAPS_NAMED = 10
_NS = 1e-9


@dataclass
class Op:
    name: str          # instruction name, e.g. "fusion.12"
    module: str        # program it ran in, e.g. "jit_step"
    pallas: bool       # a compiled Pallas kernel (tpu_custom_call)
    seconds: float     # duration
    self_seconds: float


@dataclass
class Reduction:
    window_s: float
    busy_s: float                      # mean over the chips read
    ops: List[Op] = field(default_factory=list)    # first chip read
    gaps: List[Tuple[str, float]] = field(default_factory=list)  # longest

    def kernel_seconds(self, pred) -> Tuple[float, int]:
        """Summed device time and call count of the ops ``pred`` picks."""
        picked = [o for o in self.ops if pred(o)]
        return sum(o.seconds for o in picked), len(picked)

    def top_ops(self, k: int = 10) -> List[List]:
        tot: Dict[str, float] = {}
        for o in self.ops:
            key = f"{o.module}/{o.name}"
            tot[key] = tot.get(key, 0.0) + o.self_seconds
        return [[n, s] for n, s in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> List[List]:
        return [[n, s] for n, s in self.gaps[:k]]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _instr_name(text: str) -> str:
    head = text.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def _self_times(events):
    """events: [(start, end, ...)] of one line, nested by containment.
    Returns the self time of each, in input order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    child = [0.0] * len(events)
    stack: List[int] = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            child[stack[-1]] += e - s
        stack.append(i)
    return [max(ev[1] - ev[0] - c, 0.0) for ev, c in zip(events, child)]


def _host_spans(planes):
    spans = []          # (start, end, name)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                spans.append((ev.start_ns, ev.end_ns, ev.name))
    return spans


def _gap_name(gs, ge, bench_spans, host_events):
    """The innermost benchmark span and the innermost host event that
    cover the gap's midpoint, e.g. ``bench.round>PjitFunction(add)``."""
    mid = 0.5 * (gs + ge)

    def innermost(spans):
        best = None
        for s, e, n in spans:
            if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else None

    outer = innermost(bench_spans) or "no bench span"
    inner = innermost(host_events)
    return outer if inner is None or inner == outer else f"{outer}>{inner}"


def reduce_trace(path: str, chips: int = 1,
                 window_span: str = WINDOW_SPAN) -> Reduction:
    """Reduce the trace at ``path`` over the host span ``window_span``
    (the traced window), reading the first ``chips`` TPU device planes."""
    from jax.profiler import ProfileData
    return reduce_planes(list(ProfileData.from_file(path).planes), chips,
                         window_span)


def reduce_planes(planes, chips: int = 1,
                  window_span: str = WINDOW_SPAN) -> Reduction:
    """:func:`reduce_trace` over planes already read: objects with
    ``name`` and ``lines``, lines with ``name`` and ``events``, events
    with ``name``, ``start_ns`` and ``end_ns``."""
    host = _host_spans(planes)
    windows = [(s, e) for s, e, n in host if n == window_span]
    if not windows:
        raise ValueError(f"trace has no {window_span!r} host span")
    w0, w1 = windows[0]
    bench_spans = [(s, e, n) for s, e, n in host
                   if n.startswith(SPAN_PREFIX) and n != window_span
                   and e > w0 and s < w1]
    host_events = [(s, e, n) for s, e, n in host
                   if not n.startswith(SPAN_PREFIX) and e > w0 and s < w1
                   and e - s < (w1 - w0)]
    devices = sorted((p for p in planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    if len(devices) < chips:
        raise ValueError(f"trace has {len(devices)} TPU planes, "
                         f"{chips} asked for")
    busy, red = [], Reduction(window_s=(w1 - w0) * _NS, busy_s=0.0)
    for k, plane in enumerate(devices):
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((max(ev.start_ns, w0), min(ev.end_ns, w1), ev.name)
                      for ev in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else [])
                      if ev.end_ns > w0 and ev.start_ns < w1)
        merged = _union([(s, e) for s, e, _ in mods])
        busy.append(sum(e - s for s, e in merged))
        if k:
            continue
        starts = [m[0] for m in mods]
        evs = [(ev.start_ns, ev.end_ns, ev.name)
               for ev in (lines["XLA Ops"].events
                          if "XLA Ops" in lines else [])
               if ev.end_ns > w0 and ev.start_ns < w1]
        selfs = _self_times(evs)
        for (s, e, text), own in zip(evs, selfs):
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2].split("(", 1)[0] \
                if i >= 0 and s <= mods[i][1] else "?"
            red.ops.append(Op(
                name=_instr_name(text), module=module,
                pallas='custom_call_target="tpu_custom_call"' in text,
                seconds=(e - s) * _NS, self_seconds=own * _NS))
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps = sorted(((gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                       if ge > gs), key=lambda g: g[0] - g[1])
        red.gaps = [(_gap_name(gs, ge, bench_spans, host_events),
                     (ge - gs) * _NS) for gs, ge in gaps[:GAPS_NAMED]]
    red.busy_s = sum(busy) / len(busy) * _NS
    return red

