"""End-to-end metrics from the harness's records, and the context the
per-layer readers (``metrics/<name>.py``) read from.

Every request is timed on the harness's clock from when it was due (open
loop) or sent (closed loop).  Tails are taken over every request or gap
of the window, rates over all of the window's work and time.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from . import roofline as R


def p95(xs) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if len(xs) else None


def _served(run):
    return [r for r in run.records if r.in_window and r.source]


def hit_latencies_ms(run) -> List[float]:
    return [(r.done_s - r.req.due_s) * 1e3 for r in _served(run)
            if r.source != "cloud"]


def ttft_ms(run) -> List[float]:
    return [(r.tok_s[0] - r.req.due_s) * 1e3 for r in _served(run)
            if r.source == "cloud" and r.tok_s]


def itl_ms(run, closed: bool) -> List[float]:
    W = run.window_s
    out = []
    for r in run.records:
        if not r.tok_s or (not closed and not r.in_window):
            continue
        t = np.asarray(r.tok_s)
        if closed:
            t = t[(t >= 0.0) & (t <= W)]
        out.extend((np.diff(t) * 1e3).tolist())
    return out


def tokens_in_window(run) -> int:
    W = run.window_s
    return int(sum(((np.asarray(r.tok_s) >= 0.0)
                    & (np.asarray(r.tok_s) <= W)).sum()
                   for r in run.records if r.tok_s))


def end_to_end(run, closed: bool) -> Dict[str, Optional[float]]:
    return {
        "hit_p95_ms": p95(hit_latencies_ms(run)),
        "ttft_p95_ms": p95(ttft_ms(run)),
        "itl_p95_ms": p95(itl_ms(run, closed)),
        "out_tok_s": tokens_in_window(run) / run.window_s,
        "setup_s": run.setup["setup_s"],
    }


class Context:
    """What a per-layer reader may read: the run's records and counters
    (``run``), the reduced trace (``trace``), the configuration
    (``model``, ``serving``, ``coic``) and the chip's ``peak``."""

    def __init__(self, run, trace, config: dict, peak: dict):
        self.run, self.trace, self.peak = run, trace, peak
        self.model, self.serving = config["model"], config["serving"]
        self.coic = config["coic"]

    # -- host clock ---------------------------------------------------
    def step_ms(self) -> Optional[float]:
        n = self.run.steps_in_window
        return self.run.window_s * 1e3 / n if n else None

    def _window_work(self):
        """(model FLOPs, paged-attention bytes of decode) of the tokens
        the window computed: descriptors of the requests sent in it,
        prompts whose first token came in it, tokens decoded in it."""
        m, W = self.model, self.run.window_s
        k_layers, page = self.coic["k_layers"], self.serving["kv_page"]
        flops = kv_bytes = 0.0
        for r in self.run.records:
            P = len(r.req.prompt)
            if 0.0 <= r.submit_s <= W:
                flops += R.prompt_flops(m, k_layers, P, logits=False)
            for k, t in enumerate(r.tok_s):
                if not 0.0 <= t <= W:
                    continue
                if k == 0:
                    flops += R.prompt_flops(m, m["num_layers"], P, True)
                else:
                    flops += R.decode_flops(m, P + k)
                    kv_bytes += R.paged_attention_bytes(m, page, P + k)
        return flops, kv_bytes

    def mfu(self) -> float:
        flops, _ = self._window_work()
        return 100.0 * flops / self.run.window_s / self.peak["bf16_flops"]

    # -- device trace (nothing to read where it holds no device) -------
    def _device_trace(self):
        t = self.trace
        return t if t is not None and t.devices else None

    def idle_share(self) -> Optional[float]:
        t = self._device_trace()
        return None if t is None else 100.0 * (1.0 - t.busy_s / t.window_s)

    def device_ms_per_span(self, labels, span: str) -> Optional[float]:
        t = self._device_trace()
        if t is None or not t.span_count.get(span):
            return None
        s = sum(t.device_s_by_label.get(lb, 0.0) for lb in labels)
        return 1e3 * s / t.span_count[span]

    def prefill_ms_per_ktok(self) -> Optional[float]:
        t = self._device_trace()
        toks = self.run.counters.get("window_prefill_tokens", 0)
        if t is None or not toks:
            return None
        s = sum(t.device_s_by_label.get(lb, 0.0)
                for lb in ("prefill_chunk", "admit"))
        return 1e3 * s / (toks / 1e3)

    def paged_attention_roofline(self) -> Optional[float]:
        t = self._device_trace()
        if t is None:
            return None
        secs = t.kernel_s("paged_attention", "decode")
        _, kv_bytes = self._window_work()
        if not secs or not kv_bytes:
            return None
        # the window's decode tokens against the traced window's kernel
        # time: the trace covers the same window as the records
        return 100.0 * kv_bytes / self.peak["hbm_bytes_per_s"] / secs
