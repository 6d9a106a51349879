"""Median time to first token from the due time, recorded only: above the
knee the queue grows all through the run, so this swings with the smallest
change and judges no PR."""
from benchmark.end_to_end.ttft_p95_ms import samples
from benchmark.lib.stats import percentile

LAYER, UNIT, SOURCE, MOVES = ("serve entry", "ms", "host_clock",
                              "serve_tokens_per_s")


def read(obs):
    return percentile(samples(obs), 50)
