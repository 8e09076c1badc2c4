"""The on-chip benchmark's correctness check sees each fault a served
cell can have: the timed path is broken underneath a tiny run on the CPU
(the harness's look for a chip skipped) and ``correct`` must come out
false, by the check that fault belongs to.  Faults of training (half a
batch left out) and of several chips (the exchange left out) have no
place in these one-chip serving cells."""
import numpy as np
import pytest

import bench_tiny as BT
from repro.models.transformer import DecoderLM
from repro.serving import engine as E


def _state_unchanged(mp):
    real = DecoderLM.decode_step

    def decode_step(self, params, cache, tokens, lengths, **kw):
        logits, _, _ = real(self, params, cache, tokens, lengths, **kw)
        return logits, cache, lengths
    mp.setattr(DecoderLM, "decode_step", decode_step)


def _token_altered(mp):
    real = E.ServingEngine._retire

    def _retire(self, slot):
        a = self.active[slot]
        a.generated[2] = (a.generated[2] + 1) % self.model.cfg.vocab_size
        return real(self, slot)
    mp.setattr(E.ServingEngine, "_retire", _retire)


def _answer_altered(mp):
    real = E.ServingEngine._finalize

    def _finalize(self, rid, *, tokens, source, **kw):
        if source != "cloud":
            tokens = np.array(tokens)
            tokens[0] += 1
        return real(self, rid, tokens=tokens, source=source, **kw)
    mp.setattr(E.ServingEngine, "_finalize", _finalize)


def _ladder_misses(mp):
    real = E.route_flat

    def route_flat(org, desc, nodes, clusters):
        res = real(org, desc, nodes, clusters)
        res.tier[:] = E.TIER_MISS
        return res
    mp.setattr(E, "route_flat", route_flat)


@pytest.mark.parametrize("fault,check", [
    (_state_unchanged, "logit_gap"),
    (_token_altered, "logit_gap"),
    (_answer_altered, "payload_mismatches"),
    (_ladder_misses, "ladder_disagreements"),
], ids=["state_unchanged", "token_altered", "answer_altered",
        "ladder_decision"])
def test_fault_makes_the_run_incorrect(tmp_path, monkeypatch, fault, check):
    fault(monkeypatch)
    res = BT.run_tiny(tmp_path, seed=7)
    assert res["correct"] is False
    c = res["checks"][check]
    assert c["value"] is None or c["value"] > c["limit"]
