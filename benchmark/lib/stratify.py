"""Stratified draws of a mix's lengths: the distributions the mix states,
without the seed's luck in them.

`lib/workload.build_schedule` draws every request's lengths independently.
Where a window serves a few hundred requests and a request's cost depends
on its lengths (a prompt's prefill bucket, the slot-time of its output),
the sample IS much of the reading: two seeds differ by what they drew and
in which order, not by what the program did. A mix with

    "stratify": {"size": k, "order_seed": n}

keeps the schedule's arrival times, tenants and each request's stream of
prompt ids, and redraws the lengths so that every `k` consecutive requests
hold one draw from each k-th of the stated distribution:

    u = (order[j] + jitter[j]) / k,   length = clip(round(Q(u)))

with Q the quantile function of the mix's length distribution (what
`sample_len` samples) and `clip` its `lo`/`hi`. `order`, a permutation of
0..k-1 for every block, comes from `order_seed`: it is a parameter of the
MIX, the same in every run, because the order in which long and short
requests meet the window's edges moves the reading as much as the draw
does. `jitter`, where inside its stratum a length falls, comes from the
run's seed, like the arrival times and the prompts' ids. The marginal
distribution of a length is the stated one, clips and all; prompt and
output lengths are ordered apart, so they stay uncorrelated; what goes is
the independence of one request's lengths from its neighbours' and from
one seed to the next. The seeding burst is one block of its own, its
output scale U(lo, hi) stratified the same way. Every draw is a pure
function of (seed, stream name, index), as in `lib/workload`: a block's
draws do not depend on the horizon.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from benchmark.lib.workload import _SUBSHIFT, Request, Stream


def quantile_len(dist, u, cap):
    """`sample_len`'s draw at the quantile `u` in (0, 1) of `dist`."""
    kind = dist["kind"]
    lo = int(dist.get("lo", 1))
    hi = min(int(dist.get("hi", cap)), int(cap))
    u = min(max(float(u), 1e-12), 1.0 - 1e-12)
    if kind == "uniform":
        raw = float(lo + int(u * (hi + 1 - lo)))
    elif kind == "lognormal":
        raw = math.exp(math.log(float(dist["median"]))
                       + float(dist["sigma"]) * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"no quantile function for length kind {kind!r}")
    return max(lo, min(int(round(raw)), hi))


def _strata(order_s, jitter_s, block, size):
    """`size` quantiles, one from each `size`-th of (0, 1): the order of
    the strata from `order_s`, the place inside each from `jitter_s`."""
    base = block * size
    order = np.argsort([order_s.u01(base + j) for j in range(size)])
    return [(int(order[j]) + jitter_s.u01(base + j)) / size
            for j in range(size)]


def restratify(schedule, mix, seed, vocab, max_seq_len):
    """`schedule` (build_schedule's, of a one-tenant mix) with its lengths
    redrawn in strata of `mix["stratify"]["size"]` requests; arrival times,
    indices and each request's stream of prompt ids are kept."""
    size = int(mix["stratify"]["size"])
    if len(mix["tenants"]) != 1:
        raise ValueError("stratify: a mix of one tenant")
    tenant = mix["tenants"][0]
    names = ("prompt_len", "gen_len", "burst_scale")
    order = [Stream(int(mix["stratify"]["order_seed"]),
                    f"{mix['name']}/strata/{n}") for n in names]
    jitter = [Stream(seed, f"{mix['name']}/jitter/{n}") for n in names]
    ptok_s = Stream(seed, f"{mix['name']}/prompt_tok")
    burst = mix.get("seed_burst") or {}
    n_burst = min(int(burst.get("count", 0)), len(schedule))
    lo, hi = burst.get("new_scale", [1.0, 1.0])
    out = []

    def block(requests, number, width, scaled):
        up, un, us = (_strata(o, j, number, width)
                      for o, j in zip(order, jitter))
        for j, r in enumerate(requests):
            plen = quantile_len(tenant["prompt"], up[j], max_seq_len - 1)
            nlen = quantile_len(tenant["new"], un[j], max_seq_len - plen)
            if scaled:
                nlen = max(1, int(round(nlen * (lo + (hi - lo) * us[j]))))
            prompt = ptok_s.randint_block(r.index << _SUBSHIFT, plen, 1,
                                          vocab)
            out.append(Request(r.index, r.t_due, r.tenant, prompt,
                               int(nlen)))

    if n_burst:
        block(schedule[:n_burst], 0, n_burst, True)
    rest = schedule[n_burst:]
    for b in range(0, len(rest), size):
        # block numbers from 1 << 20 on: the burst's draws are block 0's
        block(rest[b:b + size], (1 << 20) + b // size, size, False)
    return out
