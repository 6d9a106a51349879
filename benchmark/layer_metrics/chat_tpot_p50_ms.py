"""Median of the requests' mean gap between output tokens, recorded only
(see chat_ttft_p50_ms)."""
from benchmark.end_to_end.tpot_p50_ms import samples
from benchmark.lib.stats import percentile

LAYER, UNIT, SOURCE, MOVES = ("serve entry", "ms", "host_clock",
                              "serve_tokens_per_s")


def read(obs):
    return percentile(samples(obs), 50)
