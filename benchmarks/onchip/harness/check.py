"""Is what the timed path produced correct?

Four comparisons, on the window's own requests, after the window closed
and the program's state was freed:

* ladder: each request's outcome (served by the ladder, or by the model)
  against the outcome the seed fixed: a repeat of a hot scene must hit,
  a new scene must miss.  Exact: limit 0.
* payload: each hit returns the tokens of the set-up miss that inserted
  its scene.  Exact: limit 0.
* never served: requests of the window with no answer a minute or two
  after the close (the drain's limit).  Limit 0.
* model: a sample of the window's misses, drawn from the seed with the
  longest in it, is run through the float32 reference (prompt and served
  tokens); the widest gap by which a served token's logit lies below the
  reference's best logit at its position.  The limit is the
  configuration's ``check.logit_gap_limit``, set from sound runs and from
  the reference in float8 (see ``PERF.md``).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import weights as W


def ladder(run) -> Dict[str, int]:
    """Disagreements with the seed's hit/miss plan, hits whose tokens are
    not their scene's, and requests of the window never served."""
    disagree = mismatch = never = 0
    for r in run.records:
        if not r.in_window:
            continue
        if not r.source:
            never += 1
            continue
        hit = r.source != "cloud"
        disagree += int(hit != r.req.expect_hit)
        if hit:
            want = run.hot_tokens.get(r.req.scene)
            mismatch += int(want is None
                            or not np.array_equal(r.tokens, want))
    return {"disagree": disagree, "mismatch": mismatch, "never": never}


def sample(run, n: int, seed: int) -> List:
    """``n`` finished misses of the window drawn from the seed, the
    longest (prompt and answer) among them."""
    done = [r for r in run.records if r.in_window and r.source == "cloud"
            and r.tokens is not None and len(r.tokens)]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].req.prompt) + len(done[i].tokens))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    return [done[i] for i in [longest] + rest[:n - 1]]


def logit_gaps(ref, model: dict, seed: int, recs, precisions=("f32",)):
    """The reference's readings over ``recs`` (see ``configs/*.py``):
    per precision, the widest gap of the served token (``f32``) or of the
    token that precision puts first (others)."""
    seqs = [np.concatenate([r.req.prompt, r.tokens]).astype(np.int32)
            for r in recs]
    starts = [len(r.req.prompt) for r in recs]
    out = ref.score(model, seed, seqs, starts, W.make_leaf, precisions)
    gaps = {}
    for p, rows in out.items():
        key = "served" if p == "f32" else "at_top"
        gaps[p] = float(max((row["best"] - row[key]).max() for row in rows))
    return gaps
