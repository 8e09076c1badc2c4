"""The on-chip benchmark's traffic generator: every seed gives the same
work in another order, the same seed the same traffic, and which requests
hit is fixed by the seed."""
import json

import numpy as np
import pytest

import bench_tiny as BT
from harness import traffic as T

AR_HITS = json.loads((BT.BENCH / "traffic" / "ar-hits.json").read_text())
BACKLOG = json.loads((BT.BENCH / "traffic" / "code-backlog.json").read_text())
KW = dict(seconds=30.0, vocab=64000, slots=32, max_len=1024, chunk=128)


def _plan(seed, mix=AR_HITS, **kw):
    return T.plan(mix, seed=seed, **{**KW, **kw})


def test_same_seed_same_traffic():
    a, b = _plan(2 ** 31 + 7), _plan(2 ** 31 + 7)
    assert len(a.window) == len(b.window)
    for x, y in zip(a.window, b.window):
        assert (x.due_s, x.node, x.scene, x.expect_hit) == \
            (y.due_s, y.node, y.scene, y.expect_hit)
        assert np.array_equal(x.prompt, y.prompt)


def test_seeds_share_the_work_in_another_order():
    a, b = _plan(1), _plan(2)
    assert len(a.window) == len(b.window) == round(AR_HITS["rate_per_s"] * 30)
    gaps = [np.sort(np.diff([0.0] + [r.due_s for r in p.window]))
            for p in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    assert sorted(len(h) for h in a.hot) == sorted(len(h) for h in b.hot)
    assert [r.due_s for r in a.window] != [r.due_s for r in b.window]
    assert all(0.0 < r.due_s < 30.0 for r in a.window)


def test_hits_are_fixed_by_the_seed():
    p = _plan(3)
    hot = [r for r in p.window if r.expect_hit]
    assert len(hot) == round(AR_HITS["hot_share"] * len(p.window))
    for r in hot:
        assert np.array_equal(r.prompt, p.hot[r.scene])
    new = [r.prompt.tobytes() for r in p.window if not r.expect_hit]
    seen = {h.tobytes() for h in p.hot}
    assert len(set(new)) == len(new) and not seen & set(new)
    assert all(r.scene == -1 for r in p.window if not r.expect_hit)
    lens = [len(r.prompt) - AR_HITS["image_tokens"] for r in p.window]
    assert min(lens) >= 8 and max(lens) <= 128


def test_warm_up_covers_every_shape_the_window_reaches():
    p = _plan(4)
    cap = AR_HITS["max_submit_per_step"]
    misses = [g for g in p.warm if not g.requests[0].expect_hit]
    # descriptor batches 1, 2, 4 at one node, each prefilled alone
    assert [len(g.requests) for g in misses[:3]] == [1, 2, 4]
    for g in misses[:3]:
        assert g.wait and len({r.node for r in g.requests}) == 1
    # one group of slots / 2 + 1 prompts sweeps the prefill chunk's row
    # buckets: its first prompts are still mid-prefill when the last joins
    assert len(misses[3].requests) == 17
    served = {r.scene for g in misses for r in g.requests}
    assert set(range(AR_HITS["hot_scenes"])) <= served
    for g in misses:
        for r in g.requests:
            if r.scene >= 0:
                assert r.node == p.hot_node[r.scene]
    # peer hits: widths B = 1, 2, 4, with n = 1 .. B of them from one owner
    peer = [g.requests for g in p.warm if g.requests[0].expect_hit]
    assert [len(g) for g in peer] == [1, 2, 2, 4, 4, 4, 4]
    for g in peer:
        node = g[0].node
        owners = {p.hot_node[r.scene] for r in g} - {node}
        assert len(owners) == 1 and all(r.node == node for r in g)


def test_closed_loop_warm_up_reaches_every_chunk_row_bucket():
    p = _plan(6, BACKLOG, slots=64, max_len=2560, chunk=64)
    sizes = [len(g.requests) for g in p.warm]
    assert sizes == [1, 2] * 4 + [33]
    assert not any(g.wait for g in p.warm[:-1]) and p.warm[-1].wait
    # 33 prompts joining 2 a step: the first needs 18 chunks of 64
    assert {len(r.prompt) for r in p.warm[-1].requests} == {64 * 18}


def test_descriptor_buckets_of_a_mix():
    assert T.desc_buckets(AR_HITS, 1024) == {1024: 704}
    assert T.desc_buckets(BACKLOG, 2560) == {256: 256, 512: 512,
                                            1024: 1024, 2048: 2048}


def test_closed_loop_prompts_are_unshared():
    p = _plan(5, BACKLOG, slots=64, max_len=2560, chunk=64)
    assert p.loop == "closed" and p.clients == 128
    lens = [len(r.prompt) for r in p.window]
    assert min(lens) >= 256 and max(lens) <= 2048
    heads = {r.prompt[:64].tobytes() for r in p.window[:500]}
    assert len(heads) == 500
    assert not any(r.expect_hit for r in p.window)


@pytest.mark.parametrize("dist", [
    {"dist": "loguniform", "min": 256, "max": 2048},
    {"dist": "lognormal", "min": 8, "max": 128, "median": 32, "sigma": 0.8},
])
def test_quantile_lengths(dist):
    q = T.quantiles(dist, 1000)
    assert q.min() >= dist["min"] and q.max() <= dist["max"]
    assert np.all(np.diff(q) >= 0)
    if dist["dist"] == "lognormal":
        assert abs(np.median(q) - dist["median"]) <= 1
