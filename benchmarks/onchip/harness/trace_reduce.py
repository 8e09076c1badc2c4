"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the
device's operations (the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane) and the host spans (the engine's and the harness's
``TraceAnnotation`` names, on the ``/host:CPU`` plane).  Both are on the
profiler's clock.  ``reduce`` works on plain lists, so a test can hand it
a small trace written by hand:

* busy: the union of the intervals in which an operation ran, per
  device, inside the traced window; idle is the rest;
* each operation is labelled with the innermost host span open when it
  ended (``harness`` when none was), which says which engine phase the
  device was working for: the engine waits for its device work inside
  the span that issued it (``descriptor``, ``lookup``, ``decode``) or the
  one after (a prefill chunk's result is read in ``admit``).  The end is
  taken, not the start, because the host's and the device's clocks in
  the trace agree only to about a millisecond, and a program starts
  right after its span opens but ends well before it closes;
* each idle gap is split over the innermost host spans it overlaps: what
  the host was doing while the device waited.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

HOST_SPANS = ("step", "schedule", "descriptor", "lookup", "admit", "prefill",
              "prefill_chunk", "decode", "retire", "arrivals")
OUTSIDE = "harness"


@dataclasses.dataclass
class Op:
    name: str
    start: float        # seconds on the profiler's clock
    end: float
    text: str = ""      # name plus the op's descriptive stats, for matching


@dataclasses.dataclass
class Reduced:
    devices: int                        # device planes in the trace
    window_s: float
    busy_s: float                       # mean over devices
    ops: List[Tuple[Op, str]]           # (op, host label) inside the window
    # device time per host label: the union of its ops' intervals
    span_count: Dict[str, int]          # host spans that began in the window
    span_s: Dict[str, float]            # total host time per span name
    device_s_by_label: Dict[str, float]
    idle_by_label: Dict[str, float]

    def kernel_ops(self, needle: str, label: Optional[str] = None):
        return [op for op, lb in self.ops
                if needle in op.text and (label is None or lb == label)]

    def kernel_s(self, needle: str, label: Optional[str] = None) -> float:
        return float(sum(op.end - op.start
                         for op in self.kernel_ops(needle, label)))

    def breakdown(self, top: int = 10) -> dict:
        """The operations that took most device time (nested ones
        counted in their own right too) and the longest idle gaps, each
        named by the host span it fell in."""
        by_op: Dict[str, float] = defaultdict(float)
        for op, lb in self.ops:
            by_op[f"{lb}/{op.name}"] += op.end - op.start
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, float(v)] for k, v in ops],
                "idle_gaps": [[k, float(v)] for k, v in gaps]}


def _is_span(name: str) -> bool:
    return name in HOST_SPANS or name.startswith("probe:")


def load(path: str):
    """(device ops per device, host spans, the traced window) from an
    xplane file.  The window is the harness's ``window`` span."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: List[List[Op]] = []
    spans: List[Tuple[float, float, str]] = []
    window = None
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    # TPU op events are named by their HLO text
                    # ("%fusion.3 = bf16[...] fusion(...)"): keep the
                    # instruction's name, match kernels on the whole text
                    st = {k: v for k, v in ev.stats}
                    text = " ".join([ev.name] + [str(st[k]) for k in
                                                 ("long_name", "hlo_op",
                                                  "tf_op") if k in st])
                    name = ev.name.split(" = ")[0].lstrip("%")
                    ops.append(Op(name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9,
                                  text))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "window":
                        window = (ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9)
                    elif _is_span(ev.name):
                        spans.append((ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9,
                                      ev.name))
    return devices, spans, window


def load_dir(trace_dir: str):
    """``load`` of the one xplane file ``jax.profiler`` wrote under
    ``trace_dir``."""
    import glob
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {files}")
    devices, spans, window = load(files[0])
    if window is None:
        raise RuntimeError("the trace holds no 'window' span")
    return devices, spans, window


def remove_dir(trace_dir: str) -> None:
    import shutil
    shutil.rmtree(trace_dir, ignore_errors=True)


def segments(spans: Sequence[Tuple[float, float, str]]):
    """Cut the timeline into pieces labelled by the innermost open span
    (spans nest, as one thread's annotations do).  Returns sorted arrays
    (starts, ends, labels); time in no span is not covered."""
    order = sorted(spans, key=lambda s: (s[0], -s[1]))
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    t = [-np.inf]

    def emit(upto):
        if stack and upto > t[0]:
            out.append((t[0], upto, stack[-1][1]))
        t[0] = max(t[0], upto)

    for s, e, name in order:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    starts = np.array([o[0] for o in out])
    ends = np.array([o[1] for o in out])
    return starts, ends, [o[2] for o in out]


def _label_at(segs, t: float) -> str:
    starts, ends, labels = segs
    i = int(np.searchsorted(starts, t, side="left")) - 1
    if i >= 0 and t <= ends[i]:
        return labels[i]
    return OUTSIDE


def _merge(ops: List[Op], w0: float, w1: float):
    iv = sorted((max(o.start, w0), min(o.end, w1)) for o in ops
                if o.end > w0 and o.start < w1)
    merged: List[List[float]] = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _split_gap(segs, g0: float, g1: float, acc: Dict[str, float]) -> None:
    starts, ends, labels = segs
    covered = 0.0
    i = max(0, int(np.searchsorted(ends, g0, side="right")))
    while i < len(starts) and starts[i] < g1:
        ov = min(ends[i], g1) - max(starts[i], g0)
        if ov > 0:
            acc[labels[i]] += ov
            covered += ov
        i += 1
    if g1 - g0 - covered > 0:
        acc[OUTSIDE] += g1 - g0 - covered


def reduce(devices: List[List[Op]], spans, w0: float, w1: float) -> Reduced:
    """Reduce a trace to the window [w0, w1) (profiler clock, seconds)."""
    segs = segments(spans)
    busy, ops = [], []
    dev_by_label: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for dev in devices:
        merged = _merge(dev, w0, w1)
        busy.append(sum(e - s for s, e in merged))
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                _split_gap(segs, prev, s, idle)
            prev = max(prev, e)
        by_label: Dict[str, List[Op]] = defaultdict(list)
        for op in dev:
            if op.end <= w0 or op.start >= w1:
                continue
            lb = _label_at(segs, op.end)
            ops.append((op, lb))
            by_label[lb].append(op)
        for lb, lops in by_label.items():
            dev_by_label[lb] += sum(e - s for s, e in _merge(lops, w0, w1))
    n = max(1, len(devices))
    count: Dict[str, int] = defaultdict(int)
    span_s: Dict[str, float] = defaultdict(float)
    for s, e, name in spans:
        if w0 <= s < w1:
            count[name] += 1
            span_s[name] += e - s
    return Reduced(devices=len(devices), window_s=w1 - w0, busy_s=float(sum(busy)) / n, ops=ops,
                   span_count=dict(count), span_s=dict(span_s),
                   device_s_by_label={k: v / n for k, v in
                                      dev_by_label.items()},
                   idle_by_label={k: v / n for k, v in idle.items()})
