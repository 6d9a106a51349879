"""How late the load generator submitted: 95th percentile of submit time
minus due time over the requests due in the window. A starved generator
offers less than the cell's rate, and must not read as a slow (or, where
tails judge, a fast) server."""
from benchmark.lib.stats import lateness_ms, percentile

LAYER, UNIT, SOURCE, MOVES = ("load generator", "ms", "host_clock",
                              "serve_tokens_per_s")


def read(obs):
    late = [lateness_ms(r["t_due"], r["t_submit"])
            for r in obs.get("rows", []) if r["t_submit"] is not None]
    return percentile(late, 95)
