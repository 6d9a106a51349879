"""Median, over the finished requests due in the window, of a request's
mean gap between output tokens, (t_done - t_first) / (n_out - 1). The
server stamps no single token, so the tail of single gaps is not
available (ROADMAP S2)."""
from benchmark.lib.stats import percentile, tpot_ms

UNIT, SOURCE = "ms", "host_clock"


def samples(obs):
    out = [tpot_ms(r["t_first"], r["t_done"], r["n_out_wanted"])
           for r in obs.get("rows", []) if r["finished"] and not r["error"]]
    return [x for x in out if x is not None]


def read(obs):
    return percentile(samples(obs), 50)
