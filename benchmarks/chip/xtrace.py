"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

``load`` reads the device ops and the host spans out of the file with
``jax.profiler.ProfileData``; ``reduce`` works on plain tuples, so the
arithmetic is tested on constructed traces without a chip:

* busy time: the union of the device-op intervals inside the window,
  averaged over the chips; the idle share is 1 - busy / window;
* per-op self time (time not covered by an op nested inside it on the
  same line), the ten largest;
* idle gaps between busy intervals, each labelled by the innermost host
  span that covers its midpoint, summed per label, the ten largest;
* per host span, the device time of the ops matching a kernel name that
  start inside it (the roofline metrics read this per engine step).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# (name, start_ns, end_ns, chip)
Op = Tuple[str, float, float, int]
# (name, start_ns, end_ns, stats)
Span = Tuple[str, float, float, dict]

HOST_PLANE = "/host:CPU"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    chips: int
    planes: Tuple[str, ...] = ()


def find_xplane(directory: str) -> Optional[str]:
    hits = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def _stats(ev) -> dict:
    out = {}
    for s in getattr(ev, "stats", ()):
        try:
            out[s[0]] = s[1]
        except (TypeError, IndexError):
            pass
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    chips = 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = chips
            chips += 1
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, chip))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name.startswith("tf_"):
                    continue              # runtime threads, not the host
                for ev in line.events:
                    if ev.name.startswith("$"):
                        continue          # python tracer frames
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, _stats(ev)))
    return Trace(ops, spans, chips, tuple(p.name for p in pd.planes))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(ops: List[Op]) -> Dict[str, float]:
    """Per-name time not covered by ops nested inside (same chip line)."""
    by_chip: Dict[int, List[Op]] = defaultdict(list)
    for op in ops:
        by_chip[op[3]].append(op)
    out: Dict[str, float] = defaultdict(float)
    for chip_ops in by_chip.values():
        chip_ops.sort(key=lambda o: (o[1], -o[2]))
        stack: List[list] = []        # [name, end, nested time, duration]
        for name, s, e, _ in chip_ops:
            while stack and stack[-1][1] <= s:
                n, _end, child, dur = stack.pop()
                out[n] += dur - child
            if stack:
                stack[-1][2] += e - s
            stack.append([name, e, 0.0, e - s])
        while stack:
            n, _end, child, dur = stack.pop()
            out[n] += dur - child
    return out


class _Covers:
    """Innermost host span covering an instant: with nested spans that is
    the latest-starting one still open, found by walking back from the
    last span that starts before it."""

    def __init__(self, spans: List[Span]):
        self.spans = sorted(spans, key=lambda sp: sp[1])
        self.starts = [sp[1] for sp in self.spans]

    def label(self, t: float, walk: int = 512) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        for j in range(i, max(i - walk, -1), -1):
            name, s, e, _ = self.spans[j]
            if e > t:
                return name[:80]
        return "(no host span)"


# gaps shorter than this are the seams between back-to-back ops
SEAM_NS = 5_000


def reduce(tr: Trace, window: Tuple[float, float], top: int = 10) -> dict:
    """Busy/idle, top ops and labelled idle gaps of ``tr`` in ``window``
    (ns). ``busy_s`` is averaged over the chips."""
    w0, w1 = window
    ops = [(n, max(s, w0), min(e, w1), c) for n, s, e, c in tr.ops
           if e > w0 and s < w1]
    chips = max(tr.chips, 1)
    busy = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    covers = _Covers([sp for sp in tr.spans if sp[2] > w0 and sp[1] < w1])
    for chip in range(chips):
        iv = _union([(s, e) for _, s, e, c in ops if c == chip])
        busy += sum(e - s for s, e in iv)
        edges = [w0] + [x for se in iv for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= SEAM_NS:
                gaps[covers.label((a + b) / 2)] += (b - a) / chips
            elif b > a:
                gaps["(seams between ops)"] += (b - a) / chips
    busy /= chips
    selft = _self_times(ops)
    dev_ops = sorted(selft.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, t / 1e9 / chips] for n, t in dev_ops],
        "idle_gaps": [[n, t / 1e9] for n, t in idle],
    }


def kernel_time_in_spans(tr: Trace, span_name: str, kernel: str
                         ) -> List[Tuple[dict, float]]:
    """For each host span called ``span_name``: its stats and the device
    seconds of ops whose name contains ``kernel`` and that start inside
    it, averaged over the chips."""
    kops = sorted((s, e) for n, s, e, _ in tr.ops if kernel in n)
    starts = [s for s, _ in kops]
    out = []
    chips = max(tr.chips, 1)
    for name, s, e, stats in tr.spans:
        if name != span_name:
            continue
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        out.append((stats, sum(b - a for a, b in kops[i:j]) / 1e9 / chips))
    return out
