"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData`` alone.  Device planes are the
``/device:...`` planes; on each, the operations that ran are the events
of its op line (``XLA Ops``), less the control-flow events that only
enclose others (a ``while`` loop spans all of its body's operations).
Host spans are the ``bench.*`` ``TraceAnnotation`` events the harness
writes around each call into the program.  The traced window runs from
the ``bench.trace_start`` mark to the ``bench.trace_stop`` mark.

* busy: the union of a device's op intervals inside the window;
* kernel: the union of the Pallas kernel's intervals (the Mosaic custom
  call, ``custom_call_target="tpu_custom_call"`` in the op's HLO text;
  the engine-step kernel is the only one on the simulator's path), and
  busy outside them.  The kernel's event count is kept as a cross-check
  only: the cycles traced are counted from the whole runs the harness
  traced, so a path without the kernel, or a kernel that steps several
  cycles per call, reads the same work;
* idle gaps: the stretches of the window in which a device ran nothing,
  each named by the innermost ``bench.*`` span around its midpoint.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: the line of a device plane whose events are the operations run
OP_LINE = "XLA Ops"
#: text that marks a Pallas (Mosaic) kernel in an op's HLO text
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
START_MARK, STOP_MARK = "bench.trace_start", "bench.trace_stop"


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi)`` that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[Tuple[float, float, str]], t: float) -> str:
    """Name of the shortest span that contains ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside bench spans"


def is_kernel(hlo_text: str) -> bool:
    return KERNEL_MARK in hlo_text


def short_name(hlo_text: str) -> str:
    """``%fusion.93 = s32[1024] fusion(...)`` -> ``fusion.93``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def leaves(ops: List[tuple]) -> List[tuple]:
    """Drop the events that enclose the next one (control flow: a
    ``while`` op's event covers its whole body)."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or not (nxt[0] < o[1] and nxt[1] <= o[1])]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Dict:
    """Events of one trace file, in seconds: per device plane its ops as
    ``(start, end, name, kernel?)``, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans = []
    names: Dict[str, tuple] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for ev in line.events:
                    nm = names.get(ev.name)
                    if nm is None:
                        nm = names[ev.name] = (short_name(ev.name),
                                               is_kernel(ev.name))
                    s = ev.start_ns * 1e-9
                    ops.append((s, s + ev.duration_ns * 1e-9) + nm)
            if ops:
                devices[plane.name] = leaves(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return {"devices": devices, "spans": spans}


def reduce(raw: Dict, window: Optional[Interval] = None) -> Dict:
    """Busy, kernel and idle time of the traced window (``window``, else
    the start and stop marks), per device and averaged over devices,
    plus the breakdown the result line carries: the ten operations with
    the most device time (mean over devices) and the ten longest idle
    gaps, named by what the host was doing."""
    spans = raw["spans"]
    if window is None:
        marks = {n: s for s, e, n in spans if n in (START_MARK, STOP_MARK)}
        window = (marks[START_MARK], marks[STOP_MARK])
    lo, hi = window
    per_dev = {}
    op_time: Dict[str, float] = collections.defaultdict(float)
    gap_list = []
    for dev, ops in sorted(raw["devices"].items()):
        busy = merge(clip([(s, e) for s, e, _, _ in ops], lo, hi))
        kern = merge(clip([(s, e) for s, e, _, k in ops if k], lo, hi))
        for s, e, nm, _ in ops:
            if e > lo and s < hi:
                op_time[nm] += min(e, hi) - max(s, lo)
        busy_s = length(busy)
        kernel_s = length(kern)
        per_dev[dev] = dict(busy_s=busy_s, kernel_s=kernel_s,
                            other_s=busy_s - kernel_s,
                            kernel_calls=sum(1 for s, e, _, k in ops
                                             if k and lo <= s < hi))
        gap_list += [(e - s, s, e) for s, e in gaps(busy, lo, hi)]
    n = max(len(per_dev), 1)
    mean = {k: sum(d[k] for d in per_dev.values()) / n
            for k in ("busy_s", "kernel_s", "other_s", "kernel_calls")}
    longest = sorted(gap_list, reverse=True)[:10]
    return dict(
        window_s=hi - lo, devices=per_dev, n_devices=len(per_dev), **mean,
        breakdown=dict(
            device_ops=[[nm, t / n] for nm, t in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:10]],
            idle_gaps=[[innermost(spans, 0.5 * (s + e)), g]
                       for g, s, e in longest]))


def reduce_dir(log_dir: str) -> Dict:
    return reduce(load(find_xplane(log_dir)))
