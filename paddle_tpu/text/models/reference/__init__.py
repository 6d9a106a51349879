"""Plain float32 `jax.numpy` references of the served models: the
published forward pass with no cache, no batching and no kernel, what the
system's paths are compared with (tests/, and a copy under benchmark/lib)."""
