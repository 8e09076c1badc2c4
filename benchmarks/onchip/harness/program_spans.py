"""The program's own spans in a traced run, beside the device's busy time.

``trace_reduce`` keeps the ten engine phases and ``probe:*``.  The
program names more: every host read of a device value (``d2h:<what>``),
the step's uploads (``h2d:<what>``) and its host passes (``emit``,
``chunk_prep``).  ``load`` reads the xplane under a run's trace directory
once and keeps all of those, so a reader can say how often the host
waits on the device and how much device idle time those waits hold:

* a span counts where it began inside the harness's ``window`` span;
* device idle is split over the innermost of these spans open in each
  gap, as ``trace_reduce.reduce`` splits it over its own set.

The JAX runtime's own host events (dispatch, argument handling) share
the host plane with these spans and are left out: nested inside a
program span they would take the gaps from it.

A reader returns ``None`` where the trace holds no device (off the chip)
or no ``d2h:`` span (a program that does not name its reads).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import re
from typing import List, Optional, Tuple

from . import trace_reduce as TR

PROGRAM_SPANS = TR.HOST_SPANS + ("emit", "chunk_prep")
PREFIXES = ("probe:", "d2h:", "h2d:")
SYNC_PREFIXES = ("d2h:", "h2d:")

Span = Tuple[float, float, str]


def is_program_span(name: str) -> bool:
    return name in PROGRAM_SPANS or name.startswith(PREFIXES)


@dataclasses.dataclass
class ProgramTrace:
    devices: List[List[TR.Op]]
    spans: List[Span]                  # every program span in the trace
    window: Tuple[float, float]
    reduced: TR.Reduced                # the window over all of ``spans``

    def began(self, pred) -> List[Span]:
        w0, w1 = self.window
        return [s for s in self.spans if w0 <= s[0] < w1 and pred(s[2])]

    def steps(self) -> int:
        return len(self.began(lambda n: n == "step"))

    def idle_s(self, prefixes) -> float:
        """Device idle (mean over devices) whose innermost program span
        starts with one of ``prefixes``."""
        return float(sum(v for k, v in self.reduced.idle_by_label.items()
                         if k.startswith(prefixes)))

    def step_idle_s(self) -> float:
        """Device idle inside ``step`` spans, whatever span is innermost."""
        steps = [s for s in self.spans if s[2] == "step"]
        red = TR.reduce(self.devices, steps, *self.window)
        return float(red.idle_by_label.get("step", 0.0))


def build(devices, spans, window) -> ProgramTrace:
    return ProgramTrace(devices, list(spans), window,
                        TR.reduce(devices, spans, *window))


@functools.lru_cache(maxsize=1)
def load(trace_dir: str) -> ProgramTrace:
    """The ``ProgramTrace`` of the one xplane file under ``trace_dir``."""
    import jax
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {files}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    devices: List[List[TR.Op]] = []
    spans: List[Span] = []
    window = None
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            devices.append([
                TR.Op("", ev.start_ns * 1e-9,
                      (ev.start_ns + ev.duration_ns) * 1e-9)
                for line in plane.lines if line.name == "XLA Ops"
                for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == "window" or is_program_span(name):
                        s = (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9, name)
                        if name == "window":
                            window = s[:2]
                        else:
                            spans.append(s)
    if window is None:
        raise RuntimeError("the trace holds no 'window' span")
    return build(devices, spans, window)


def of(ctx) -> Optional[ProgramTrace]:
    """The run's ``ProgramTrace``, or ``None`` where the trace has no
    device or the program names no host read."""
    if ctx.trace is None or not ctx.trace.devices:
        return None
    t = load(ctx.run.trace_dir)
    if not t.devices or not t.steps() or not t.began(
            lambda n: n.startswith("d2h:")):
        return None
    return t


def syncs_per_step(ctx) -> Optional[float]:
    t = of(ctx)
    if t is None:
        return None
    return len(t.began(lambda n: n.startswith("d2h:"))) / t.steps()


def sync_idle_ms(ctx) -> Optional[float]:
    t = of(ctx)
    if t is None:
        return None
    return 1e3 * t.idle_s(SYNC_PREFIXES) / t.steps()


def syncs_per_lookup(ctx) -> Optional[float]:
    t = of(ctx)
    if t is None:
        return None
    lookups = sorted(s[:2] for s in t.began(lambda n: n == "lookup"))
    if not lookups:
        return None
    starts = [a for a, _ in lookups]       # one thread's lookups: disjoint
    inside = 0
    for s, _, _ in t.began(lambda n: n.startswith("d2h:")):
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < lookups[i][1]:
            inside += 1
    return inside / len(lookups)
