"""Window seconds per dispatched decode beat in a cell below the knee,
where the beat sets the gap between a stream's tokens: prefills that stall
the beat, and the time no stream was active, are inside it. `beat_ms`'s
arithmetic under a name of its own, because a per-layer metric names the
one end-to-end metric that it moves."""
from benchmark.layer_metrics.beat_ms import read  # noqa: F401

LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "ms", "program_counter",
                              "tpot_p50_ms")
