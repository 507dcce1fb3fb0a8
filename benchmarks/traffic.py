"""The one traffic generator. A traffic mix is a data file of parameters
(``benchmarks/traffic/<name>.json``); a cell (``benchmarks/cells/<workload>
.json``) fixes the rate or the number of clients. Nothing here knows a mix
by name.

What arrives is a *source*: one request (chat), a document that is asked
several questions, or any group of requests that share a prefix of their
own. Sources arrive on a schedule (``open`` loop) or are taken in turn by a
fixed number of clients (``closed`` loop). A prompt is

    [one of ``pool`` shared prefixes]  +  [the source's own prefix]  +  tail

as exact token ids from the model's vocabulary.

Time is cut into segments of ``segment_s`` seconds. Every segment of a cell
is the same schedule: the same number of sources, the gaps between arrivals
(the quantiles of the exponential distribution at the cell's rate, in one
fixed shuffled order, scaled to fill the segment) and the lengths (the
quantiles of each length distribution, paired once). The seed draws the
token ids and nothing else, so every seed, every warm-up segment and every
segment of the window offers the same work at the same times with other
contents. The system under test completes a few requests a second at
best; at that size the order of a dozen requests decides a percentile, and
a schedule ordered by the seed measured the seed (PERF.md, PR 23). Warm-up
segments and the measured window are segments of one continuous process: a
source of one segment asks its later questions in the next.
"""

from __future__ import annotations

import json
import math
import os
import random
import statistics
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
_NORMAL = statistics.NormalDist()


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    with open(os.path.join(HERE, "cells", f"{workload}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ distributions


def quantile(dist: dict, u: float) -> float:
    """Inverse CDF of a distribution from a traffic file at ``u`` in (0,1),
    clipped to its ``lo``/``hi``."""
    kind = dist["dist"]
    if kind == "const":
        x = dist["value"]
    elif kind == "uniform":
        x = dist["lo"] + u * (dist["hi"] - dist["lo"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "geometric":
        x = math.ceil(math.log(1.0 - u) / math.log(1.0 - 1.0 / dist["mean"]))
    elif kind == "exponential":
        x = -dist["mean"] * math.log(1.0 - u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(max(x, dist.get("lo", x)), dist.get("hi", x))


def stratified(dist: dict, n: int, rng: random.Random,
               integer: bool = True) -> list:
    """``n`` values at the mid-points of ``n`` equal slices of the
    distribution, in an order drawn from ``rng``: the same multiset for
    every seed."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [int(round(v)) for v in vals]
    rng.shuffle(vals)
    return vals


def zipf_choices(pool: int, exponent: float, n: int,
                 rng: random.Random) -> list:
    """``n`` pool indices with Zipf shares, largest-remainder rounded, in an
    order drawn from ``rng``."""
    w = [1.0 / (k + 1) ** exponent for k in range(pool)]
    shares = [n * x / sum(w) for x in w]
    counts = [int(s) for s in shares]
    for k in sorted(range(pool), key=lambda k: shares[k] - counts[k],
                    reverse=True)[:n - sum(counts)]:
        counts[k] += 1
    out = [k for k in range(pool) for _ in range(counts[k])]
    rng.shuffle(out)
    return out


# ----------------------------------------------------------------- requests


@dataclass
class Request:
    due: float              # seconds from the start of the timeline
    prompt: list            # token ids
    max_tokens: int
    source: str             # which source it belongs to
    turn: int               # its place among the source's requests
    measured: bool = False  # due inside the measured window
    # filled in by the load generator
    sent: float = -1.0
    first: float = -1.0     # first streamed token
    last: float = -1.0      # last streamed token
    tokens: int = 0
    ok: bool = False
    error: str = ""


class Generator:
    """Requests of one cell. ``vocab`` is the model's vocabulary size;
    ``seed`` is the run's ``--seed``."""

    WARM_SALT = 7919      # warm-up segments differ from any window's
    SHAPE_SEED = 20260927

    def __init__(self, mix: dict, cell: dict, vocab: int, seed: int):
        self.mix, self.cell = mix, cell
        self.vocab, self.seed = vocab, seed
        self.segment_s = float(cell["segment_s"])
        self.closed = mix["loop"] == "closed"
        if self.closed:
            # a closed loop has no arrival rate: a segment is one request
            # per client
            self.per_segment = int(cell["clients"])
        else:
            self.per_segment = max(1, round(cell["rate_per_s"]
                                            * self.segment_s))
        pool = mix.get("pool")
        self.pool = []
        if pool:
            # shared prefixes outlive warm-up: drawn from the run's seed
            # alone, so warm-up and window share them as deployments do
            n = int(quantile(pool["tokens"], 0.5))
            self.pool = [self._tokens(("pool", k), n)
                         for k in range(pool["size"])]

    def lead_segments(self) -> int:
        """Segments before the window whose sources still ask inside it."""
        life = self.mix.get("lifetime_s", 0.0)
        return math.ceil(life / self.segment_s) if life else 0

    def _tokens(self, key: tuple, n: int) -> list:
        h = [self.seed] + [abs(hash_str(str(k))) for k in key]
        return np.random.default_rng(h).integers(
            0, self.vocab, size=n).tolist()

    def segment(self, index: int, warm: bool) -> list:
        """The requests whose sources arrive in segment ``index``, with
        ``due`` relative to the segment's start (follow-up questions may
        fall past its end)."""
        # shapes, pairing and arrivals come from a generator that knows no
        # seed: every segment of a cell, warm-up or window, under any seed,
        # is the same schedule of the same sizes. The seed draws the tokens.
        shape = random.Random(self.SHAPE_SEED)
        n = self.per_segment
        mix = self.mix
        if self.closed:
            starts = [0.0] * n
        else:
            gaps = stratified({"dist": "exponential", "mean": 1.0}, n, shape,
                              integer=False)
            scale = self.segment_s / sum(gaps)
            # the gaps end to end, rotated by a fixed phase so that no
            # source arrives at the segment's very start
            phase, t0, starts = 0.37 * self.segment_s, 0.0, []
            for g in gaps:
                starts.append((t0 + phase) % self.segment_s)
                t0 += g * scale
            starts.sort()
        pool_ix = (zipf_choices(mix["pool"]["size"], mix["pool"]["zipf"], n,
                                shape) if self.pool else [None] * n)
        own = (stratified(mix["own_prefix"]["tokens"], n, shape)
               if mix.get("own_prefix") else [0] * n)
        per_source = (stratified(mix["requests_per_source"], n, shape)
                      if mix.get("requests_per_source") else [1] * n)
        total = sum(per_source)
        tails = stratified(mix["tail"]["tokens"], total, shape)
        outs = stratified(mix["output"]["tokens"], total, shape)
        gaps_in = (stratified(mix["gap_s"], total, shape, integer=False)
                   if mix.get("gap_s") else [0.0] * total)
        order = list(range(n))
        shape.shuffle(order)
        first = [sum(per_source[:s]) for s in range(n)]
        tag = "w" if warm else "m"
        out = []
        for slot, s in enumerate(order):
            sid = f"{tag}{index}.{slot}"
            head = list(self.pool[pool_ix[s]]) if self.pool else []
            head += self._tokens((sid, "own"), own[s]) if own[s] else []
            due = starts[slot]
            for turn in range(per_source[s]):
                j = first[s] + turn
                if turn:
                    due += gaps_in[j]
                out.append(Request(
                    due=due,
                    prompt=head + self._tokens((sid, turn), tails[j]),
                    max_tokens=outs[j], source=sid, turn=turn))
        out.sort(key=lambda r: r.due)
        return out


def hash_str(s: str) -> int:
    """A stable 63-bit hash (Python's ``hash`` of a str changes per
    process)."""
    import hashlib

    return int.from_bytes(hashlib.sha256(s.encode()).digest()[:8],
                          "big") >> 1
