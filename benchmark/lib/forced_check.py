"""Teacher-forced logits check of a served decoder: one bucket-padded
prefill and one decode beat through `net._forward_paged`, paged Pallas
kernel on against off (the counted `flag_off` gate onto
`paged_attention_ref`). Copied from chip_smoke.py (PR 22). Token equality is
not used: bf16 argmax over a 50k vocabulary with random weights flips on
rounding; logits at a tolerance do not."""
from __future__ import annotations

import numpy as np


def rel_err(got, ref):
    """|got - ref|_max / |ref|_max in float32; inf where got is not finite."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def forced_logits(net, prompt_lens, bucket, block_size, dtype, seed):
    """{"prefill": err, "decode": err}, normalised max logits error."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import tape
    from paddle_tpu.nn.kv_pool import KVBlockPool, PagedKVCache

    cfg = net.config
    lens = np.asarray(prompt_lens, np.int32)
    b = len(lens)
    per_slot = -(-(bucket + 1) // block_size)
    pool = KVBlockPool(b * per_slot, block_size)
    tables = np.asarray([pool.alloc(per_slot) for _ in range(b)], np.int32)
    heads, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    rng = np.random.RandomState(seed + 1)
    ids = np.zeros((b, bucket), np.int32)
    for i, n in enumerate(lens):
        ids[i, :n] = rng.randint(1, cfg.vocab_size, n)
    params, buffers = net.functional_state()

    def forward(params, arenas, tokens, lengths, last_index):
        with tape.no_grad():
            net.load_functional_state(params, buffers)
            caches = [PagedKVCache(k, v, jnp.asarray(tables), lengths)
                      for k, v in arenas]
            logits, new = net._forward_paged(tokens, caches,
                                             last_index=last_index)
        return logits, [(c.k, c.v) for c in new]

    def both_paths(arenas, tokens, lengths, last_index):
        out = {}
        for kernel_on in (True, False):
            paddle.set_flags({"FLAGS_use_paged_attention": kernel_on})
            try:
                # the flag is read at trace time and jit caches by function
                # identity: a fresh lambda per path forces a fresh trace
                out[kernel_on] = jax.jit(lambda *a: forward(*a))(
                    params, arenas, tokens, lengths, last_index)
            finally:
                paddle.set_flags({"FLAGS_use_paged_attention": True})
                net.load_functional_state(params, buffers)
        return out

    arenas = pool.arenas(cfg.num_layers, heads, hd, dtype)
    pre = both_paths(arenas, jnp.asarray(ids), jnp.zeros((b,), jnp.int32),
                     jnp.asarray(lens - 1))
    nxt = jnp.argmax(pre[False][0], axis=-1).astype(jnp.int32)
    dec = both_paths(pre[False][1], nxt[:, None], jnp.asarray(lens), None)
    return {"prefill": rel_err(pre[True][0], pre[False][0]),
            "decode": rel_err(dec[True][0], dec[False][0])}
