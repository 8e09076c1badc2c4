"""The one traffic generator.  A mix is a JSON file of parameters
(``traffic/<name>.json``); this module turns it and a seed into prompts,
nodes, due times and the expected ladder outcome of every request.

Every seed gets the same multiset of sizes and gaps in another order:
lengths and inter-arrival gaps are the quantiles ``(i + 1/2) / n`` of
their distributions, in an order drawn from the seed, so two seeds
differ in which request is long and when it comes, not in how much work
the window holds.  The order is stratified (``stratified``): every block
of consecutive requests holds one value from each stratum of the
distribution, and the misses of a hit mix are spread one to a block, so
no seed bunches the long gaps, the long prompts or the misses into one
stretch of the window.  Token ids, scene choice and node assignment are
drawn from the seed.

Open loop (``"loop": "open"``): ``round(rate * seconds)`` requests due
inside the window from ``users`` users homed on ``nodes`` edge nodes
(``users / nodes`` each).  A share ``hot_share`` of them repeat, exactly,
the prompt of one of ``hot_scenes`` scenes (rotated-Zipf popularity per
node, copied from ``repro.data.workload._rotated_zipf``) that set-up
serves once as a miss at the scene's home node; the rest are scenes never
seen before and never repeated.  So which requests hit is fixed by the
seed: a repeat must hit, a new scene must miss.

Closed loop (``"loop": "closed"``): ``clients_per_slot * slots`` clients,
each sending its next prompt when its last one completes; every prompt is
new, so the ladder misses.

A prompt is ``image_tokens`` image positions (the stub vision tower's
patch ids) followed by a text of ``text_len`` tokens.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int                    # position in the window's schedule
    due_s: float                  # offset from the window's start (open)
    node: int                     # the user's home edge node
    prompt: np.ndarray
    scene: int                    # hot scene id, or -1 for a new scene
    expect_hit: bool


@dataclasses.dataclass
class Plan:
    loop: str
    hot: List[np.ndarray]         # hot scene prompts (served in set-up)
    hot_node: List[int]           # each hot scene's home node
    warm: List["Group"]           # set-up traffic (``warm_groups``)
    window: List[Request]         # open loop: due inside the window;
                                  # closed loop: prompts in send order
    clients: int = 0              # closed loop only


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n lengths at the midpoint quantiles of ``dist``, as ints."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    kind = dist["dist"]
    if kind == "loguniform":
        x = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in q])
        x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    elif kind == "fixed":
        x = np.full(n, float(lo))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def stratified(values: np.ndarray, block: int, rng) -> np.ndarray:
    """``values`` in a seeded order in which every run of ``block``
    consecutive entries takes one value from each of ``block`` strata
    (equal runs of the sorted values), in a shuffled order; a last
    partial block takes what is left."""
    v = np.sort(np.asarray(values))
    n = len(v)
    nb = n // block
    if nb == 0:
        return rng.permutation(v)
    strata = v[:nb * block].reshape(block, nb)
    strata = np.stack([rng.permutation(row) for row in strata])
    blocks = [rng.permutation(strata[:, j]) for j in range(nb)]
    return np.concatenate(blocks + [rng.permutation(v[nb * block:])])


def arrivals(rate: float, seconds: float, rng, block: int = 10) -> np.ndarray:
    """Due times of a Poisson-like open loop: ``round(rate * seconds)``
    arrivals whose gaps are the exponential's midpoint quantiles in a
    seeded, stratified order, spread over the window."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = stratified(-np.log1p(-q), block, rng)
    t = np.cumsum(gaps)
    return t * (seconds / (t[-1] + gaps.mean()))


def rotated_zipf(pool_size: int, zipf_s: float, groups: int) -> np.ndarray:
    """(groups, pool_size) Zipf(s) popularity, the ranking rotated per
    group (``repro.data.workload._rotated_zipf``)."""
    base = np.arange(1, pool_size + 1, dtype=np.float64) ** (-zipf_s)
    probs = np.stack([np.roll(base, (g * pool_size) // groups)
                      for g in range(groups)])
    return probs / probs.sum(axis=1, keepdims=True)


class _Prompts:
    def __init__(self, mix: dict, vocab: int, rng):
        self.mix, self.vocab, self.rng = mix, vocab, rng

    def make(self, text_len: int) -> np.ndarray:
        n = int(self.mix.get("image_tokens", 0)) + int(text_len)
        return self.rng.integers(0, self.vocab, size=n).astype(np.int32)


def pow2(n: int, lo: int = 1) -> int:
    n = max(n, lo)
    return 1 << (n - 1).bit_length()


def desc_buckets(mix: dict, max_len: int, min_bucket: int = 8) -> dict:
    """The descriptor's padded prompt lengths this mix can produce
    (``ServingEngine._pad_prompts``), each with the longest prompt length
    that lands in it."""
    img = int(mix.get("image_tokens", 0))
    out = {}
    for L in range(img + mix["text_len"]["min"],
                   img + mix["text_len"]["max"] + 1):
        out[min(pow2(L, min_bucket), max_len)] = L
    return out


@dataclasses.dataclass
class Group:
    """Set-up requests sent together (at most ``max_submit_per_step`` a
    step); with ``wait`` the next group waits until these are prefilled."""
    requests: List[Request]
    wait: bool


def warm_groups(mix: dict, prompts: "_Prompts", *, slots: int,
                max_len: int, chunk: int, hot=(),
                hot_node=()) -> List[Group]:
    """Set-up traffic that makes every program the window can run compile
    before it opens:

    * for each descriptor length bucket, groups of 1, 2, 4 .. up to
      ``max_submit_per_step`` new scenes at one node: each descriptor batch
      and each per-node lookup width (in an open loop each also alone in
      the prefill chunk: its 1, 2, 4 .. row buckets);
    * one group of ``slots / 2 + 1`` prompts, long enough that all are
      mid-prefill when the last one joins: as they join and leave the
      prefill chunk its row count passes through every bucket up to
      ``slots``;
    * every hot scene once, as a miss at its home node (the window's hits
      return these misses' tokens), filling the group above first;
    * with hot scenes, for each lookup width B and each n <= B, a group of
      B requests at one node of which n are scenes homed at one other
      node: the peer rung's gather, touch and admission shapes.
    """
    cap, nodes = int(mix["max_submit_per_step"]), int(mix["nodes"])
    img = int(mix.get("image_tokens", 0))
    open_loop = mix["loop"] == "open"
    free = list(range(len(hot)))
    groups: List[Group] = []

    def fresh(length: int, node: int) -> Request:
        return Request(-1, 0.0, node, prompts.make(length - img), -1, False)

    def hot_or_fresh(length: int, node: int) -> Request:
        if free:
            j = free.pop(0)
            return Request(-1, 0.0, hot_node[j], hot[j], j, False)
        return fresh(length, node)

    buckets = desc_buckets(mix, max_len)
    widths = [1 << i for i in range(cap.bit_length())]
    for L in buckets.values():
        for k in widths:
            node = len(groups) % nodes
            groups.append(Group([fresh(L, node) for _ in range(k)],
                                open_loop))
    L = max(buckets.values())
    n = slots // 2 + 1
    # the first of them still chunking when the n-th joins (n / cap steps
    # later): one chunk more than that, within the mix's longest prompt
    L_n = min(L, chunk * (-(-n // cap) + 1))
    groups.append(Group([hot_or_fresh(max(L_n, img + mix["text_len"]["min"]),
                                      i % nodes) for i in range(n)], True))
    while free:
        groups.append(Group([hot_or_fresh(L, 0)
                             for _ in range(min(n, len(free)))], True))
    # peer hits: n scenes of owner o at node g, padded to B with scenes
    # homed at g (local hits); a scene is asked at a node once, since a
    # peer hit is admitted there
    asked = [set() for _ in range(nodes)]
    for B in widths if len(hot) else []:
        for k in range(1, B + 1):
            g = len(groups) % nodes
            o = (g + 1) % nodes
            peers = [j for j in range(len(hot))
                     if hot_node[j] == o and j not in asked[g]][:k]
            local = [j for j in range(len(hot)) if hot_node[j] == g][:B - k]
            asked[g].update(peers)
            groups.append(Group([Request(-1, 0.0, g, hot[j], j, True)
                                 for j in peers + local], True))
    return groups


def plan(mix: dict, *, seed: int, seconds: float, vocab: int, slots: int,
         max_len: int, chunk: int, rate: Optional[float] = None,
         window_seed: Optional[int] = None) -> Plan:
    """The whole run's traffic for ``seed``.  ``rate`` and
    ``window_seed`` (a knee sweep's windows) change the window's traffic
    and keep the hot set and set-up."""
    rng = np.random.default_rng(
        [int(seed if window_seed is None else window_seed), 0x0A5C3E])
    prompts = _Prompts(mix, vocab, rng)
    nodes = int(mix["nodes"])
    text = mix["text_len"]
    if mix["loop"] == "closed":
        clients = int(mix["clients_per_slot"]) * slots
        # enough prompts for the window at any speed: each client sends at
        # most one prompt per engine step
        n = int(mix["max_requests"])
        lens = stratified(quantiles(text, n), 10, rng)
        window = [Request(i, 0.0, (i % clients) % nodes,
                          prompts.make(int(lens[i])), -1, False)
                  for i in range(n)]
        warm = warm_groups(mix, prompts, slots=slots, max_len=max_len,
                           chunk=chunk)
        return Plan("closed", [], [], warm, window, clients)

    # the hot set and the set-up traffic come from a stream of their own,
    # so a sweep over rates keeps them
    hot_rng = np.random.default_rng([int(seed), 0x407])
    hot_prompts = _Prompts(mix, vocab, hot_rng)
    H = int(mix.get("hot_scenes", 0))
    hot, hot_node = [], []
    if H:
        hot = [hot_prompts.make(int(L))
               for L in hot_rng.permutation(quantiles(text, H))]
        probs = rotated_zipf(H, float(mix["zipf_s"]), nodes)
        hot_node = [int(np.argmax(probs[:, j])) for j in range(H)]
    warm = warm_groups(mix, hot_prompts, slots=slots, max_len=max_len,
                       chunk=chunk, hot=hot, hot_node=hot_node)

    users = int(mix["users"])
    rate = float(mix["rate_per_s"] if rate is None else rate)
    due = arrivals(rate, seconds, rng)
    n = len(due)
    n_hot = int(round(float(mix.get("hot_share", 0.0)) * n)) if H else 0
    if 0 < n_hot < n:
        # one miss to each block of n / (n - n_hot) requests
        per = n / (n - n_hot)
        is_hot = np.ones(n, bool)
        for b in range(n - n_hot):
            lo, hi = int(round(b * per)), int(round((b + 1) * per))
            is_hot[lo + int(rng.integers(hi - lo))] = False
    else:
        is_hot = np.arange(n) < n_hot
    lens = stratified(quantiles(text, n), 10, rng)
    node = rng.integers(0, users, size=n) % nodes
    window = []
    for i in range(n):
        if is_hot[i]:
            scene = int(rng.choice(H, p=probs[node[i]]))
            p = hot[scene]
        else:
            scene, p = -1, prompts.make(int(lens[i]))
        window.append(Request(i, float(due[i]), int(node[i]), p, scene,
                              bool(is_hot[i])))
    return Plan("open", hot, hot_node, warm, window)
