"""Engine spans on the profiler's clock.

``ServingEngine(tracer=...)`` opens and closes its spans (``step``,
``schedule``, ``descriptor``, ``lookup``, ``probe:<rung>``, ``admit``,
``prefill_chunk``, ``decode``, ``retire``) through the tracer it is given.
This one turns each span into a ``jax.profiler.TraceAnnotation``, so the
spans land in the device trace beside the device's operations and the
reduction can say what the host was doing in each gap.  The modeled
request timelines the engine also emits are dropped: they are not times.
"""
from __future__ import annotations

import jax

from repro.obs.trace import NullTracer


class ProfilerTracer(NullTracer):
    enabled = True

    def __init__(self):
        self._open = []

    def begin(self, name, *, cat="engine", pid=0, tid=0, ts=None,
              args=None):
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        self._open.append(ann)

    def end(self, *, pid=0, tid=0, ts=None):
        self._open.pop().__exit__(None, None, None)

    def span(self, name, *, cat="engine", pid=0, tid=0, args=None):
        return jax.profiler.TraceAnnotation(name)

    def request_timeline(self, *args, **kwargs):
        return None
