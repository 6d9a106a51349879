"""95th percentile, over the requests due in the window that got a first
token, of first token minus the time the request was DUE (open loop). p95
and not p99: with a few hundred requests it is the highest percentile with
ten samples beyond it. Requests that failed or never started count in
`failed`, not here."""
from benchmark.lib.stats import percentile, ttft_ms

UNIT, SOURCE = "ms", "host_clock"


def samples(obs):
    return [ttft_ms(r["t_due"], r["t_first"]) for r in obs.get("rows", [])
            if r["t_first"] is not None and not r["error"]]


def read(obs):
    return percentile(samples(obs), 95)
