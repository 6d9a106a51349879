"""What the benchmark and `ServeLoop` hold every served decoder to
(text/models/decoder.py, `PagedDecoder`'s docstring): the five nets are
the one scaffold; their leaves are, by name, shape, dtype AND order, what
they were before there was a scaffold (benchmark/drivers/
serve_open_loop_ref.py seats its weights by name and refuses a net whose
names differ; `paddle.seed` follows creation order); and a decode step
over a batch with one slot on the trash block returns logits, the caches
in spec order and the counts `serve_counters` reads."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nn.kv_pool import KVBlockPool, paged_caches
from paddle_tpu.text import models
from paddle_tpu.text.models.decoder import PagedDecoder

HELD = (4, 8)       # experts 4..11 of the router's 16

# the leaves of each net at `Config.tiny(dtype="bfloat16")`, taken from
# the parent of the PR that made the scaffold (282b663): the net's own,
# then each kind of block's as "name shape[ dtype]" (the configuration's
# dtype unless said), and which kind each layer is
_LATENT = ("attn_norm 64; ffn_norm 64; attn.q_a 64x32; attn.q_norm 32; "
           "attn.q_b 32x96; attn.kv_a 64x{a}; attn.kv_norm {r}; "
           "attn.kv_b {r}x128; attn.o 64x64")
_DENSE = "ffn.gate 64x96; ffn.up 64x96; ffn.down 96x64"
_EXPERTS = ("{0}router_weight 64x{1}; {0}router_bias {1} float32; "
            "{0}gate 8x64x32; {0}up 8x64x32; {0}down 8x32x64")
_SHARED = "ffn.shared_gate 64x32; ffn.shared_up 64x32; ffn.shared_down 32x64"
_SUB = "; ".join(f"sub.{i}.{leaf}" for i in (0, 1) for leaf in
                 (_LATENT.format(a=24, r=16) + "; " + _DENSE).split("; "))
_WINDOW = ("attn_norm 64; ffn_norm 64; attn.qkv 64x{}; attn.g 64x{}; "
           "attn.o {}x64")
_SINK = "attn_norm 64; ffn_norm 64; attn.qkv 64x{}; attn.o 256x64"
_MOE = _EXPERTS.format("ffn.", 16)
NETS = {
    "KimiK2": dict(
        config="KimiK2Config", kw=dict(experts_held=HELD),
        top="embed 256x64; norm 64; head 64x256",
        blocks={"A": f"{_LATENT.format(a=40, r=32)}; {_DENSE}",
                "B": f"{_LATENT.format(a=40, r=32)}; {_MOE}; {_SHARED}"},
        layers="ABB",
        counted=[((2, 8), "int32")]),
    "LongCatFlash": dict(
        config="LongCatFlashConfig", kw=dict(experts_held=HELD),
        top="embed 256x64; norm 64; head 64x256",
        blocks={"A": f"{_SUB}; {_EXPERTS.format('experts.', 24)}"},
        layers="AA",
        counted=[((2, 8), "int32"), ((2, 3), "int64")]),
    "Laguna": dict(
        config="LagunaConfig", kw=dict(experts_held=HELD),
        top="embed 256x64; norm 64; head 64x256",
        blocks={"A": f"{_WINDOW.format(128, 4, 64)}; {_DENSE}",
                "B": f"{_WINDOW.format(160, 6, 96)}; {_MOE}; {_SHARED}",
                "C": f"{_WINDOW.format(128, 4, 64)}; {_MOE}; {_SHARED}"},
        layers="ABBBC",
        counted=[((4, 8), "int32"), ((3,), "int32")]),
    "MiMoV2Flash": dict(
        config="MiMoV2Config", kw=dict(experts_held=HELD),
        top="embed 256x64; norm 64; head 64x256",
        blocks={"A": f"{_SINK.format(544)}; {_DENSE}",
                "B": f"{_SINK.format(704)}; attn.sinks 16 float32; {_MOE}",
                "C": f"{_SINK.format(544)}; {_MOE}"},
        layers="ABBBBCB",
        counted=[((6, 8), "int32"), ((3,), "int32")]),
    "OlmoHybrid": dict(
        config="OlmoHybridConfig", kw={},
        top="embed 128x64; norm 64; head 64x128",
        blocks={"A": "mixer_norm 64; ffn_norm 64; mixer.qkv 64x64; "
                     "mixer.conv 4x64; mixer.g 64x32; mixer.b 64x2; "
                     "mixer.a 64x2; mixer.A_log 2 float32; "
                     "mixer.dt_bias 2 float32; mixer.o_norm 16; "
                     f"mixer.o 32x64; {_DENSE}",
                "B": "mixer_norm 64; ffn_norm 64; mixer.qkv 64x192; "
                     "mixer.q_norm 64; mixer.k_norm 64; mixer.o 64x64; "
                     f"{_DENSE}"},
        layers="AAABAAAB",
        counted=[((2,), "int32")]),
}


def leaves_of(want):
    def parse(text, prefix=""):
        for leaf in text.split("; "):
            name, shape, *dtype = leaf.split(" ")
            yield (prefix + name, tuple(int(n) for n in shape.split("x")),
                   dtype[0] if dtype else "bfloat16")
    out = list(parse(want["top"]))
    for i, kind in enumerate(want["layers"]):
        out += parse(want["blocks"][kind], f"blocks.{i}.")
    return out


@pytest.mark.parametrize("name", list(NETS))
def test_a_served_decoder_is_the_scaffold_with_the_leaves_it_had(name):
    want = NETS[name]
    paddle.seed(0)
    config = getattr(models, want["config"]).tiny(dtype="bfloat16",
                                                  **want["kw"])
    net = getattr(models, name)(config)
    net.eval()
    assert isinstance(net, PagedDecoder) and net.config is config
    assert len(net.blocks) == config.num_layers == len(want["layers"])
    got = [(n, tuple(p.shape), re.sub(r"^.*\.", "", str(p.dtype)))
           for n, p in net.named_parameters()]
    assert got == leaves_of(want)

    # a decode step: slot 0 owns blocks 1 and 2 and holds 19 tokens, slot
    # 1 is parked on the trash block
    spec = net.paged_cache_spec()
    assert len(spec) == net.LAYER_CACHES * len(net.blocks)
    arenas = KVBlockPool(8, 16).arenas_for(spec, jnp.bfloat16, slots=2)
    tables = jnp.asarray([[1, 2, 0, 0], [0, 0, 0, 0]], jnp.int32)
    caches = paged_caches(spec, arenas, tables,
                          jnp.asarray([19, 0], jnp.int32))
    logits, new_caches, *counted = net._forward_paged(
        jnp.asarray([[5], [0]], jnp.int32), caches)
    assert (logits.shape, logits.dtype) == ((2, config.vocab_size),
                                            jnp.float32)
    assert np.isfinite(np.asarray(logits[0])).all()
    assert len(new_caches) == len(spec)
    for layer, old, new in zip(spec, caches, new_caches):
        assert type(new) is layer.cache
        assert [(x.shape, x.dtype) for x in new] \
            == [(x.shape, x.dtype) for x in old]
    assert [(c.shape, str(c.dtype)) for c in counted] == want["counted"]
    counters = net.serve_counters("decode", counted, 1)
    assert counters and set(counters) <= set(net.SERVE_STATS)
    assert set(net.SERVE_GAUGES) <= set(net.SERVE_STATS)
    if "moe_decode_tokens" in counters:   # the parked slot is routed nowhere
        assert counters["moe_decode_tokens"] == 1
        assert counters["moe_decode_pairs_held"] \
            <= counted[0].shape[0] * config.num_experts_per_tok
    assert net.prefill_tile(net.PREFILL_TILE * net.WHOLE_TILES) is None
    assert net.prefill_tile(net.PREFILL_TILE * 4) == net.PREFILL_TILE


def test_a_window_nets_rotary_tables_are_traced_in_the_layers_order():
    """The order is part of the program's text, which keys the compile
    cache: a set's order changed with the interpreter's hash seed and cost
    `laguna_agent_mixed_sat` ~100 s of `setup_s` every other process
    (PERF.md section 6, PR 49)."""
    from paddle_tpu.text.models.laguna import FULL, SLIDING
    for kinds in ([FULL, SLIDING, SLIDING], [SLIDING, FULL, SLIDING]):
        net = models.Laguna(models.LagunaConfig.tiny(
            num_layers=3, layer_types=kinds,
            num_attention_heads_per_layer=[4, 4, 4]))
        ids = jnp.zeros((1, 4), jnp.int32)
        _, rope = net._embed(ids, jnp.arange(4)[None])
        assert list(rope) == kinds[:2]
