"""The benchmark's own traffic generator: (mix, seed) -> a request schedule.

Copied in PR 23 from paddle_tpu/traffic/workload.py (PR 17) and cut to what
a cell needs, so that the program's copy may change and the yardstick does
not. Kept: every draw is a pure function of (seed, stream name, index)
through splitmix64, so one seed replays byte-identically and no draw depends
on iteration order; the arrival and length grammars. Dropped: hybrid recsys
tenants, resume state. Added: a seeding burst at t=0 and vectorised prompt
tokens (numpy uint64, same values as the scalar stream).

A mix is a data file (benchmark/traffic/<name>.json); this module is the
one general generator that reads it. Times are schedule seconds from 0; the
driver maps them onto perf_counter.
"""
from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass

import numpy as np

_MASK64 = (1 << 64) - 1
_NORMAL_XOR = 0xD6E8FEB86659FD93  # second stream for Box-Muller
_SUBSHIFT = 20  # request k, token j draws index (k << _SUBSHIFT) | j


def _splitmix64(x):
    x = (x + 0x9E3779B97F4A7C15) & _MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK64
    return (x ^ (x >> 31)) & _MASK64


def _splitmix64_np(x):
    """The same function over a uint64 array (arithmetic wraps mod 2^64)."""
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


class Stream:
    """One named draw stream: `u01(i)` is a pure function of (seed, name, i)."""

    __slots__ = ("key",)

    def __init__(self, seed, name):
        k = _splitmix64(int(seed) & _MASK64)
        for ch in name.encode("utf-8"):
            k = _splitmix64(k ^ ch)
        self.key = k

    def bits(self, index):
        return _splitmix64(self.key ^ _splitmix64(int(index) & _MASK64))

    def u01(self, index):
        return (self.bits(index) >> 11) * (1.0 / (1 << 53))

    def normal(self, index):
        h = self.bits(index)
        u1 = max((h >> 11) * (1.0 / (1 << 53)), 1e-12)
        u2 = (_splitmix64(h ^ _NORMAL_XOR) >> 11) * (1.0 / (1 << 53))
        return math.sqrt(-2.0 * math.log(u1)) * math.cos(2.0 * math.pi * u2)

    def randint(self, index, lo, hi):
        """Integer in [lo, hi), hi exclusive."""
        lo, hi = int(lo), int(hi)
        return lo if hi <= lo else lo + int(self.u01(index) * (hi - lo))

    def exp(self, index, rate):
        return -math.log(max(1.0 - self.u01(index), 1e-300)) / float(rate)

    def randint_block(self, base, count, lo, hi):
        """randint(base | j, lo, hi) for j in range(count), vectorised."""
        idx = np.uint64(base) | np.arange(count, dtype=np.uint64)
        bits = _splitmix64_np(np.uint64(self.key) ^ _splitmix64_np(idx))
        u = (bits >> np.uint64(11)).astype(np.float64) * (1.0 / (1 << 53))
        return (lo + (u * (hi - lo)).astype(np.int64)).astype(np.int64)


# length grammar: {"kind": ..., **params}, clipped to [lo, min(hi, cap)]
#   fixed {"value"} | uniform {"lo","hi"} inclusive |
#   lognormal {"median","sigma","lo","hi"} | pareto {"alpha","scale","lo","hi"}
def sample_len(dist, stream, index, cap):
    kind = dist.get("kind", "fixed")
    lo = int(dist.get("lo", 1))
    hi = min(int(dist.get("hi", cap)), int(cap))
    if kind == "fixed":
        raw = float(dist["value"])
    elif kind == "uniform":
        raw = float(stream.randint(index, lo, hi + 1))
    elif kind == "lognormal":
        raw = math.exp(math.log(float(dist["median"]))
                       + float(dist["sigma"]) * stream.normal(index))
    elif kind == "pareto":
        raw = float(dist.get("scale", lo)) / max(
            1.0 - stream.u01(index), 1e-12) ** (1.0 / float(dist["alpha"]))
    else:
        raise ValueError(f"unknown length sampler kind {kind!r}")
    return max(lo, min(int(round(raw)), hi))


# arrival grammar: rate(t) in requests/s
#   poisson {"rate"} | flash {"base","burst_rate","burst_at_s","burst_len_s"}
#   windows {"windows": [[dur_s, rate], ...]} (rate 0 emits nothing)
def arrival_rate(arrival, t):
    kind = arrival.get("kind", "poisson")
    if kind == "poisson":
        return float(arrival["rate"])
    if kind == "flash":
        t0 = float(arrival.get("burst_at_s", 0.0))
        if t0 <= t < t0 + float(arrival.get("burst_len_s", 1.0)):
            return float(arrival["burst_rate"])
        return float(arrival.get("base", 0.0))
    if kind == "windows":
        edge = 0.0
        for dur, rate in arrival["windows"]:
            edge += float(dur)
            if t < edge:
                return float(rate)
        return 0.0
    raise ValueError(f"unknown arrival kind {kind!r}")


def arrival_peak_rate(arrival):
    kind = arrival.get("kind", "poisson")
    if kind == "poisson":
        return float(arrival["rate"])
    if kind == "flash":
        return max(float(arrival.get("base", 0.0)),
                   float(arrival["burst_rate"]))
    if kind == "windows":
        return max([float(r) for _, r in arrival["windows"]] or [0.0])
    raise ValueError(f"unknown arrival kind {kind!r}")


@dataclass
class Request:
    index: int
    t_due: float            # schedule seconds
    tenant: str
    prompt: np.ndarray      # int64 ids in [1, vocab)
    new_tokens: int


def build_schedule(mix, seed, vocab, max_seq_len, horizon_s):
    """Every request of (mix, seed) due before `horizon_s`, in due order.

    mix keys: "name", "arrival", "tenants" [{"name","weight","prompt","new"}],
    optional "seed_burst" {"count", "new_scale": [lo, hi]}: `count` requests
    due at t=0 whose output length is scaled by a uniform draw, so that the
    slots they fill retire staggered, as in steady state.
    """
    s = lambda name: Stream(seed, f"{mix['name']}/{name}")  # noqa: E731
    arrive, thin, tenant_s = s("arrival"), s("thin"), s("tenant")
    plen_s, nlen_s, ptok_s, scale_s = (s("prompt_len"), s("gen_len"),
                                       s("prompt_tok"), s("burst_scale"))
    tenants = mix["tenants"]
    weights = np.asarray([float(t.get("weight", 1.0)) for t in tenants])
    cum = np.cumsum(weights / weights.sum())
    burst = mix.get("seed_burst") or {}
    n_burst = int(burst.get("count", 0))
    out = []

    def emit(t):
        k = len(out)
        ti = min(int(np.searchsorted(cum, tenant_s.u01(k), side="right")),
                 len(tenants) - 1)
        ten = tenants[ti]
        plen = sample_len(ten["prompt"], plen_s, k, max_seq_len - 1)
        nlen = sample_len(ten["new"], nlen_s, k, max_seq_len - plen)
        if k < n_burst:
            lo, hi = burst.get("new_scale", [1.0, 1.0])
            nlen = max(1, int(round(nlen * (lo + (hi - lo)
                                            * scale_s.u01(k)))))
        prompt = ptok_s.randint_block(k << _SUBSHIFT, plen, 1, vocab)
        out.append(Request(k, float(t), ten.get("name", "default"), prompt,
                           int(nlen)))

    for _ in range(n_burst):
        emit(0.0)
    peak = arrival_peak_rate(mix["arrival"])
    t, i = 0.0, 0
    while peak > 0.0:
        t += arrive.exp(i, peak)
        if t >= horizon_s:
            break
        lam = arrival_rate(mix["arrival"], t)
        if lam > 0.0 and thin.u01(i) * peak < lam:  # Lewis-Shedler thinning
            emit(t)
        i += 1
    return out


def schedule_digest(requests):
    """SHA-256 over a schedule's canonical bytes: the replay oracle."""
    h = hashlib.sha256()
    for r in requests:
        h.update(f"{r.index}|{r.t_due!r}|{r.tenant}|{r.new_tokens}|".encode())
        h.update(np.ascontiguousarray(r.prompt, np.int64).tobytes())
        h.update(b"\n")
    return h.hexdigest()
