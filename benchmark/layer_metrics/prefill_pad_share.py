"""Share of the rows the prefills dispatched that held no prompt token:
a prompt is padded to its bucket, a power of two, so a prompt just over a
bucket pays twice its length. `100 x (1 - prefill_tokens / prefill_rows)`,
both as differences between the first and the last `loop.stats()` sample of
the window (the whole window, not the traced seconds): `prefill_tokens`
counts the prompts' own tokens, `prefill_rows` the buckets dispatched.
Reported by the traced run like every counter metric; nothing from a
program whose `stats()` has no `prefill_rows`, or from a window without a
prefill."""
LAYER, UNIT, SOURCE, MOVES = ("serve scheduler", "%", "program_counter",
                              "serve_tokens_per_s")


def read(obs):
    samples = obs.get("samples")
    if "trace_modules" not in obs or not samples \
            or "prefill_rows" not in samples[0]:
        return None
    rows = samples[-1]["prefill_rows"] - samples[0]["prefill_rows"]
    tokens = samples[-1]["prefill_tokens"] - samples[0]["prefill_tokens"]
    if rows <= 0:
        return None
    print(f"prefill: {tokens} prompt tokens in {rows} rows dispatched over "
          "the window", flush=True)
    return 100.0 * (1.0 - tokens / rows)
