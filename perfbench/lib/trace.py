"""Reduce a profiler trace (``.xplane.pb``) of one window to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (events of its "XLA Ops" line; "XLA Modules" where a plane has no op
  line), inside the window, averaged over the device planes;
* idle gaps: the complement of that union inside the window.  Each part of
  a gap goes to the innermost of the benchmark's own host spans
  (``bench.<name>``) that covers it, and a gap is named by the span that
  takes most of it;
* top device operations: summed durations by operation name.

The window is the benchmark's ``bench.window`` host span.  A trace with no
device plane, or no device event in the window, gives ``None``: a reader
that finds nothing returns nothing.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OP_LINES = ("XLA Ops",)
FALLBACK_LINES = ("XLA Modules",)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    device_planes: int
    top_ops: list  # [[name, seconds]] by summed device time
    idle_gaps: list  # [[span name, seconds]] longest gaps first
    idle_by_span: dict  # span name -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns)


def op_name(name: str) -> str:
    """An operation's short name: a TPU op event is named by its whole HLO
    instruction (``%fusion.12 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0]


def reduce_planes(planes, top: int = 10) -> TraceSummary | None:
    """``planes``: iterable of objects with ``name`` and ``lines`` (each line
    with ``name`` and ``events`` of ``name``, ``start_ns``, ``duration_ns``),
    as ``jax.profiler.ProfileData`` gives them."""
    window = None
    spans = []
    device_lines = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            chosen = [ln for ln in lines if ln.name in OP_LINES] or [
                ln for ln in lines if ln.name in FALLBACK_LINES
            ]
            if chosen:
                device_lines.append(chosen)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b in _events(line):
                    if name == WINDOW_SPAN:
                        window = (a, b) if window is None else (min(a, window[0]), max(b, window[1]))
                    elif name.startswith(SPAN_PREFIX):
                        spans.append((name[len(SPAN_PREFIX):], a, b))
    if window is None or not device_lines:
        return None
    lo, hi = window
    busy_total = 0.0
    op_time = collections.Counter()
    gaps = []
    for lines in device_lines:
        intervals = []
        for line in lines:
            for name, a, b in _events(line):
                clipped = _clip([(a, b)], lo, hi)
                if clipped:
                    intervals.extend(clipped)
                    op_time[op_name(name)] += clipped[0][1] - clipped[0][0]
        busy = _union(intervals)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend(
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        )
    if busy_total <= 0.0:
        return None
    n_dev = len(device_lines)
    leaves = [
        sp for sp in spans
        if not any(o is not sp and sp[1] <= o[1] and o[2] <= sp[2] for o in spans)
    ]
    idle_by_span = collections.Counter()
    named = []
    for a, b in gaps:
        parts = collections.Counter()
        for name, sa, sb in leaves:
            ov = min(b, sb) - max(a, sa)
            if ov > 0:
                parts[name] += ov
        rest = (b - a) - sum(parts.values())
        if rest > 0:  # to the innermost enclosing span, if any
            outer = [sp for sp in spans if sp not in leaves and min(b, sp[2]) > max(a, sp[1])]
            parts[min(outer, key=lambda sp: sp[2] - sp[1])[0] if outer else "no benchmark span"] += rest
        for name, t in parts.items():
            idle_by_span[name] += t / 1e9 / n_dev
        named.append((parts.most_common(1)[0][0], (b - a) / 1e9))
    named.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / 1e9 / n_dev,
        device_planes=n_dev,
        top_ops=[[name, t / 1e9 / n_dev] for name, t in op_time.most_common(top)],
        idle_gaps=[[name, s] for name, s in named[:top]],
        idle_by_span=dict(idle_by_span),
    )


def reduce_file(path: str, top: int = 10) -> TraceSummary | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, top=top)
