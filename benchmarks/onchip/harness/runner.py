"""One run of one cell: build the engine, warm it up, measure the window,
drain, and keep a record of every request on the harness's own clock.

The engine is driven through its normal entry points only:
``ServingEngine.submit`` and ``ServingEngine.step``.  After each step the
harness reads what the step produced (``results`` and the tokens of the
requests in ``active``) and stamps it with ``time.perf_counter()``: the
step has returned, so those tokens are on the host.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from . import traffic as T
from . import weights as W


@dataclasses.dataclass
class Record:
    req: T.Request
    rid: int = -1
    submit_s: float = float("nan")     # harness clock, window-relative
    done_s: float = float("nan")
    tok_s: List[float] = dataclasses.field(default_factory=list)
    source: str = ""
    tokens: Optional[np.ndarray] = None
    in_window: bool = True             # open loop: due inside the window


@dataclasses.dataclass
class Run:
    records: List[Record]
    window_s: float
    steps_in_window: int
    setup: Dict[str, float]
    compiles_in_window: int
    compile_names: List[str]
    late_s: List[float]                # submit - due, open loop
    hot_tokens: Dict[int, np.ndarray]  # hot scene -> tokens of its miss
    trace_dir: Optional[str]
    memory_peak_bytes: int
    counters: Dict[str, float]


class CompileCounter:
    """Counts backend compiles and persistent-cache loads; between
    ``start`` and ``stop`` also names every program jit compiles (JAX's
    compile log, kept off the terminal)."""

    LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

    def __init__(self):
        import logging
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_loads = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        names = self.names

        class _H(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    names.append(msg.split(" with ")[0][10:])
        self._handler = _H()
        self._loggers = [logging.getLogger(n) for n in self.LOGGERS]

    def start(self) -> None:
        self.names.clear()
        for lg in self._loggers:
            lg.propagate = False
        self._loggers[0].addHandler(self._handler)
        jax.config.update("jax_log_compiles", True)

    def stop(self) -> None:
        jax.config.update("jax_log_compiles", False)
        self._loggers[0].removeHandler(self._handler)
        for lg in self._loggers:
            lg.propagate = True

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1


def build_engine(config: dict, params, tracer=None):
    from repro.configs.base import ModelConfig
    from repro.core.coic import CoICConfig
    from repro.models import build_model
    from repro.serving.engine import ServingConfig, ServingEngine

    model = build_model(ModelConfig(**config["model"]))
    sv, cc = config["serving"], config["coic"]
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=sv["slots"], max_len=sv["max_len"],
        max_new_tokens=sv["max_new_tokens"], kv_page=sv["kv_page"],
        kv_pages=sv["kv_pages"], prefill_chunk=sv["prefill_chunk"],
        attn_impl=sv["attn_impl"],
        coic=CoICConfig(descriptor="prefix", num_nodes=cc["num_nodes"],
                        capacity=cc["capacity"], threshold=cc["threshold"],
                        k_layers=cc["k_layers"],
                        lookup_impl=cc.get("lookup_impl", "auto"))),
        tracer=tracer)
    return model, eng


def _busy(eng) -> bool:
    return bool(eng.pending or eng.queue or eng.chunking or eng.active)


class Runner:
    """Holds the engine and the records; ``step`` is the one place the
    engine advances."""

    def __init__(self, eng, cap: int):
        self.eng = eng
        self.cap = cap
        self.by_rid: Dict[int, Record] = {}
        self.n_results = 0
        self.backlog: List[Record] = []
        self.t0 = time.perf_counter()
        self.steps = 0                  # engine steps since the window opened
        self.late: List[float] = []     # submit - due, since it opened

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit_backlog(self) -> None:
        take, self.backlog = self.backlog[:self.cap], self.backlog[self.cap:]
        t = self.now()
        for rec in take:
            rec.rid = self.eng.submit(rec.req.prompt, node_id=rec.req.node)
            rec.submit_s = t
            self.by_rid[rec.rid] = rec
            self.late.append(t - rec.req.due_s)

    def step(self) -> List[Record]:
        """Submit up to ``cap`` waiting requests, advance the engine one
        step, stamp what it produced; returns the requests it finished."""
        self.submit_backlog()
        eng = self.eng
        eng.step()
        t = self.now()
        self.steps += 1
        for a in eng.active.values():
            rec = self.by_rid[a.req_id]
            while len(rec.tok_s) < len(a.generated):
                rec.tok_s.append(t)
        done = []
        for r in eng.results[self.n_results:]:
            rec = self.by_rid[r.req_id]
            rec.done_s, rec.source, rec.tokens = t, r.source, r.tokens
            if r.source == "cloud":
                while len(rec.tok_s) < len(r.tokens):
                    rec.tok_s.append(t)
            done.append(rec)
        self.n_results = len(eng.results)
        return done

    def drain(self, limit_s: float) -> None:
        end = self.now() + limit_s
        while (self.backlog or _busy(self.eng)) and self.now() < end:
            self.step()


def warm_up(drv: Runner, groups: List[T.Group],
            hot_tokens: Dict[int, np.ndarray],
            drain: bool = True) -> List[Record]:
    """Send the set-up groups in order, each in steps of its own (a
    ``wait`` group until its prompts are prefilled), then drain.  Misses
    of hot scenes leave their tokens in ``hot_tokens`` (what a later hit
    must return); the first repeat waits for them."""
    eng = drv.eng
    recs: List[Record] = []
    drained = False
    for group in groups:
        if not drained and any(r.expect_hit for r in group.requests):
            drv.drain(600.0)
            drained = True
        batch = [Record(req) for req in group.requests]
        recs += batch
        drv.backlog.extend(batch)
        drv.step()
        while group.wait and (drv.backlog or eng.pending or eng.queue
                              or eng.chunking):
            drv.step()
    if not drain:
        return recs
    drv.drain(600.0)
    for rec in recs:
        if rec.req.scene >= 0 and rec.source == "cloud":
            hot_tokens.setdefault(rec.req.scene, rec.tokens)
    return recs


def open_loop(drv: Runner, window: List[T.Request], seconds: float,
              on_open=None, on_close=None):
    """Requests become due at their times; each step submits what is due
    (``cap`` a step).  The window closes after ``seconds``; what was due
    inside it is then submitted and drained outside it."""
    recs = [Record(req) for req in window]
    pending = list(recs)
    if on_open:
        on_open()
    drv.t0 = time.perf_counter()
    drv.steps = 0
    drv.late = []
    i = 0
    while True:
        now = drv.now()
        if now >= seconds:
            break
        while i < len(pending) and pending[i].req.due_s <= now:
            drv.backlog.append(pending[i])
            i += 1
        if drv.backlog or _busy(drv.eng):
            drv.step()
        elif i < len(pending):
            wait = min(pending[i].req.due_s, seconds) - drv.now()
            if wait > 0:
                with jax.profiler.TraceAnnotation("arrivals"):
                    time.sleep(wait)
    t_close = drv.now()
    steps = drv.steps
    if on_close:
        on_close()
    for rec in pending[i:]:
        rec.in_window = rec.req.due_s < seconds
    drv.backlog.extend(pending[i:])
    drv.drain(120.0)
    return recs, t_close, steps, drv.late


def closed_loop(drv: Runner, prompts: List[T.Request], clients: int,
                seconds: float, ramp_done: int, on_open=None,
                on_close=None, setup_recs=()):
    """``clients`` clients join one per step; each sends its next prompt
    when its last one completes.  Set-up (with ``setup_recs`` in flight)
    lasts until ``ramp_done`` requests have completed; then the window
    runs ``seconds``.  Tokens of set-up requests that land in the window
    count as the window's."""
    it = iter(prompts)
    recs: List[Record] = list(setup_recs)
    joined = 0
    completed = 0

    def send():
        rec = Record(next(it))
        recs.append(rec)
        drv.backlog.append(rec)

    def advance():
        nonlocal joined, completed
        if joined < clients:
            send()
            joined += 1
        for rec in drv.step():
            completed += 1
            if rec.req.index >= 0:      # a client's (not set-up's) request
                send()

    while completed < ramp_done:
        advance()
    if on_open:
        on_open()
    shift = drv.now()
    drv.t0 += shift
    for rec in recs:                   # back onto the window's clock
        rec.submit_s -= shift
        rec.done_s -= shift
        rec.tok_s = [t - shift for t in rec.tok_s]
    drv.steps = 0
    while drv.now() < seconds:
        advance()
    t_close = drv.now()
    steps = drv.steps
    if on_close:
        on_close()
    # the window's requests are those it finished, set-up's included: a
    # request spans more steps than a window holds; those in flight at
    # the close are not counted (their tokens inside the window are)
    for rec in recs:
        rec.in_window = bool(rec.source) and 0.0 <= rec.done_s <= t_close
    return recs, t_close, steps, []


def memory_peak() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def counters(eng) -> Dict[str, float]:
    st = eng.stats()
    return {"completed": st["completed"], "edge_hits": st["edge_hits"],
            "peer_hits": st["peer_hits"], "cloud": st["cloud"],
            "prefill_computed": st["prefill_tokens"]["computed"],
            "prefill_shared": st["prefill_tokens"]["shared"],
            **{f"dispatch_{k}": v for k, v in st["dispatches"].items()}}


def run(cell, seed: int, seconds: float, trace: bool, log=print,
        t_start: Optional[float] = None):
    """Set-up, window and drain of one run; returns (Run, engine).
    ``t_start``: the process's start on ``time.perf_counter``'s clock."""
    t_start = time.perf_counter() if t_start is None else t_start
    cc = CompileCounter()
    config, mix = cell.config, cell.traffic
    sv = config["serving"]
    tracer = None
    if trace:
        from .tracer import ProfilerTracer
        tracer = ProfilerTracer()
    setup: Dict[str, float] = {}

    t = time.perf_counter()
    params = W.make_params(config["model"], seed)
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t
    model, eng = build_engine(config, params, tracer)
    W.check_layout(W.layout(config["model"]),
                   {k: v.shape for k, v in model.init_shapes().items()})
    del params

    p = T.plan(mix, seed=seed, seconds=seconds,
               vocab=config["model"]["vocab_size"], slots=sv["slots"],
               max_len=sv["max_len"], chunk=sv["prefill_chunk"])
    drv = Runner(eng, int(mix["max_submit_per_step"]))
    hot_tokens: Dict[int, np.ndarray] = {}
    t = time.perf_counter()
    c0, l0 = cc.compiles, cc.cache_loads
    warm_recs = warm_up(drv, p.warm, hot_tokens, drain=p.loop == "open")

    trace_dir = None

    def on_open():
        nonlocal trace_dir
        jax.block_until_ready(eng.cache)
        # set-up's objects out of the collector's way: fewer and shorter
        # collection pauses inside the window
        gc.collect()
        gc.freeze()
        setup["warm_s"] = time.perf_counter() - t
        setup["compiles"] = cc.compiles - c0
        setup["cache_loads"] = cc.cache_loads - l0
        setup["compile_s"] = cc.compile_s
        setup["setup_s"] = time.perf_counter() - t_start
        ctr0["prefill"] = eng.prefill_tokens_computed
        cc.start()
        if trace:
            import tempfile
            trace_dir = tempfile.mkdtemp(prefix="onchip-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window_ann.append(jax.profiler.TraceAnnotation("window"))
            window_ann[0].__enter__()

    def on_close():
        jax.block_until_ready(eng.cache)
        gc.unfreeze()
        if trace:
            window_ann[0].__exit__(None, None, None)
        cc.stop()
        ctr0["window_prefill_tokens"] = (eng.prefill_tokens_computed
                                         - ctr0["prefill"])

    ctr0: Dict[str, int] = {}
    window_ann: list = []

    if p.loop == "open":
        recs, t_close, steps, late = open_loop(drv, p.window, seconds,
                                               on_open, on_close)
    else:
        recs, t_close, steps, late = closed_loop(
            drv, p.window, p.clients, seconds, int(mix["ramp_completions"]),
            on_open, on_close, setup_recs=warm_recs)
    if trace:    # after the drain, which the window's requests wait for
        jax.profiler.stop_trace()
    in_window_compiles = len(cc.names)
    mem = memory_peak()
    ctr = counters(eng)
    ctr["window_prefill_tokens"] = ctr0["window_prefill_tokens"]
    log(f"window: {steps} steps in {t_close:.3f} s, "
        f"{len(recs)} requests, compiles in window {in_window_compiles}")
    return Run(records=recs, window_s=float(seconds),
               steps_in_window=steps,
               setup=setup, compiles_in_window=in_window_compiles,
               compile_names=list(cc.names), late_s=late,
               hot_tokens=hot_tokens, trace_dir=trace_dir,
               memory_peak_bytes=mem,
               counters=ctr), eng
