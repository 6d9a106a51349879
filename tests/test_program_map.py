"""core/program_map.py: the map from a compiled program's instructions to
the scope they were made under, the programs `ServeLoop` notes, and the
rows a prefill dispatches and computes (`stats()["prefill_rows"]`,
`["prefill_live_rows"]`).

On the CPU at toy size: what a map holds, that asking for it builds
nothing, that a beat never touches it. What the compiler keeps of the
scopes on a v5e is held in tests/test_chip_smoke.py's ahead-of-time
compiles.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.core import monitor, program_map
from paddle_tpu.inference import ServeConfig, ServeLoop
from paddle_tpu.text.models.decoder import PagedDecoder
from paddle_tpu.text.models.gpt import GPT, GPTConfig
from paddle_tpu.text.models.kimi_k2 import KimiK2, KimiK2Config
from paddle_tpu.text.models.longcat_flash import (LongCatFlash,
                                                  LongCatFlashConfig)
from paddle_tpu.text.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import obs_report  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_map(tmp_path):
    """An empty map, and an empty compile cache: a scope is metadata,
    metadata is no part of a cache key, and an executable that another
    tree compiled comes back with THAT tree's scopes."""
    from jax._src import compilation_cache
    program_map.reset()
    shared = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", shared)
    compilation_cache.reset_cache()
    program_map.reset()


@jax.jit
def _inner(x):
    return jnp.tanh(x @ x)


def _toy(p, a, n):
    with jax.named_scope("layer0"):
        with jax.named_scope("attn"):
            a = _inner(a) + p["w"]
        with jax.named_scope("ffn"), jax.named_scope("gdn_step"):
            a = _inner(a) * n
    with jax.named_scope("layer1"), jax.named_scope("attn"):
        a = _inner(a)
    return a, jnp.argmax(a, -1)


def _backend_compiles(fn):
    """How many backend compiles `fn()` causes (`jax.monitoring`)."""
    seen = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    return len(seen)


def test_scope_of_innermost_word_wins():
    assert program_map.scope_of("jit(f)/layer0/attn/jit(g)/dot") == "attn"
    assert program_map.scope_of(
        "jit(f)/layer1/experts/router/jit(g)/top_k") == "router"
    assert program_map.scope_of(
        "jit(f)/layer1/linear_attn/gdn_step/pallas_call") == "linear_attn"
    # a word is a whole path component, not a substring of one
    assert program_map.scope_of("jit(attn_fn)/layer0/dot_general") is None
    assert program_map.scope_of("jit(f)/jit(_write_blocks)/iota") is None
    assert program_map.scope_of(None) is None
    assert program_map.scope_of("") is None


def test_map_of_a_jitted_toy_and_no_second_compile():
    jf = jax.jit(_toy, donate_argnums=(1,))
    p = {"w": jnp.ones((32, 32))}
    a = jnp.asarray(np.ones((32, 32), np.float32))
    args = (p, a, jnp.int32(3))
    shapes = program_map.shapes(args)      # before the call: `a` is donated
    assert _backend_compiles(lambda: jf(*args)) >= 1
    # the executable the call made is found again, not built
    assert _backend_compiles(
        lambda: program_map.note("toy", jf, shapes)) == 0
    assert program_map.labels() == ["toy"]
    m = program_map.scopes("toy")
    assert m is program_map.scopes("toy")            # parsed once
    assert m["module"] == "jit__toy"
    words = {}
    for name, path in m["ops"].items():
        words.setdefault(program_map.scope_of(path), []).append(path)
    assert {"attn", "ffn", None} <= set(words)
    # an inner jit traced once keeps each call site's scope
    assert any(p_.startswith("jit(_toy)/layer0/attn/jit(_inner)/")
               for p_ in words["attn"])
    assert any(p_.startswith("jit(_toy)/layer1/attn/jit(_inner)/")
               for p_ in words["attn"])
    assert any(p_.startswith("jit(_toy)/layer0/ffn/gdn_step/jit(_inner)/")
               for p_ in words["ffn"])
    # the argmax lies under no word
    assert any("argmax" in p_ or "reduce" in p_ for p_ in words[None])
    assert program_map.scopes("nobody noted this") is None


def test_parse_reads_every_computation_and_skips_bare_instructions():
    text = "\n".join([
        "HloModule jit_decode_step, is_scheduled=true, entry_computation"
        "_layout={()->f32[]}",
        "%body (p: f32[8]) -> f32[8] {",
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata='
        '{op_name="jit(decode_step)/layer0/experts/jit(_routed_expert_ffn)'
        '/while/body/dot_general" source_file="x.py" source_line=3}',
        "}",
        "ENTRY %main () -> f32[] {",
        "  %slice-done.3 = f32[8]{0} async-done(%slice-start.3)",
        '  ROOT %while.1 = f32[8]{0} while(%t), condition=%c, body=%body, '
        'metadata={op_name="jit(decode_step)/layer0/experts/jit(_routed_'
        'expert_ffn)/while"}',
        "}"])
    m = program_map.parse(text)
    assert m["module"] == "jit_decode_step"
    assert set(m["ops"]) == {"fusion.7", "while.1"}
    assert {program_map.scope_of(v) for v in m["ops"].values()} \
        == {"experts"}


def _rows(n):
    b = 8
    while b < n:
        b *= 2
    return b


def test_prefill_rows_are_the_buckets_dispatched():
    paddle.seed(0)
    net = GPT(GPTConfig.tiny())
    net.eval()
    monitor.reset(prefix="serve.")
    rng = np.random.RandomState(3)
    lens = (5, 9, 16, 17, 33)
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=32,
                                      block_size=16, max_seq_len=64))
    assert loop.stats()["prefill_rows"] == 0
    reqs = [loop.submit(rng.randint(1, 1024, (n,)).astype(np.int64),
                        max_new_tokens=3) for n in lens]
    loop.run_until_idle()
    stats = loop.stats()
    assert all(len(r.out) == 3 for r in reqs)
    # to the last digit: the requests' own lengths and their buckets
    assert stats["prefill_tokens"] == sum(lens) == 80
    assert stats["prefill_rows"] == sum(_rows(n) for n in lens) == 136
    assert stats["prefill_rows"] >= stats["prefill_tokens"]
    assert monitor.stat_get("serve.prefill_rows") == 136
    # a net that cuts no bucket into tiles computes what it dispatches
    assert stats["prefill_live_rows"] == 136
    assert monitor.stat_get("serve.prefill_live_rows") == 136
    said = obs_report.serving_section(
        {"values": monitor.stats("serve.")}, [])
    assert ("  prefill: 80 prompt tokens in 136 rows: 41.2% padding "
            "dispatched, 41.2% computed") in said.splitlines()
    assert obs_report.prefill_line({}) is None
    # a dump from before the second gauge says the first share alone
    assert obs_report.prefill_line(
        {"serve.prefill_tokens": 80, "serve.prefill_rows": 136}) \
        == "  prefill: 80 prompt tokens in 136 rows: 41.2% padding dispatched"
    # every program the loop traced is in the map under the scheduler's
    # own name for it, a prefill by its bucket
    assert program_map.labels() == ["serve/decode"] + sorted(
        f"serve/prefill/{b}" for b in {_rows(n) for n in lens})
    assert program_map.scopes("serve/decode")["module"] == "jit_decode_step"
    assert program_map.scopes("serve/prefill/64")["module"] == "jit_prefill"


@pytest.mark.parametrize("kind, owner", [("kimi", PagedDecoder),
                                         ("longcat", PagedDecoder),
                                         ("hybrid", OlmoHybrid)])
def test_prefill_live_rows_are_the_tiles_that_hold_a_token(
        kind, owner, monkeypatch):
    """A net that cuts a bucket into tiles (here of 16 rows) computes the
    tiles up to the prompt's end; a bucket of one tile or less runs
    whole."""
    monkeypatch.setattr(owner, "PREFILL_TILE", 16)
    monitor.reset(prefix="serve.")
    rng = np.random.RandomState(3)
    lens = (5, 16, 17, 33, 40, 64)
    loop = ServeLoop(_net(kind), ServeConfig(
        max_active=2, kv_blocks=32, block_size=16, max_seq_len=128))
    reqs = [loop.submit(rng.randint(1, 128, (n,)).astype(np.int64),
                        max_new_tokens=2) for n in lens]
    loop.run_until_idle()
    assert all(len(r.out) == 2 for r in reqs)
    stats = loop.stats()
    assert stats["prefill_tokens"] == sum(lens) == 175
    assert stats["prefill_rows"] == 8 + 16 + 32 + 64 + 64 + 64 == 248
    # buckets 8 and 16 whole (and 32, two tiles both live, in the latent
    # nets: the same count); 64, 64, 64: ceil(n / 16) * 16
    assert stats["prefill_live_rows"] == 8 + 16 + 32 + 48 + 48 + 64 == 216
    assert monitor.stat_get("serve.prefill_live_rows") == 216
    assert obs_report.prefill_line(monitor.stats("serve.")) == (
        "  prefill: 175 prompt tokens in 248 rows: 29.4% padding "
        "dispatched, 19.0% computed")


def test_a_beat_does_not_touch_the_map(monkeypatch):
    paddle.seed(0)
    net = GPT(GPTConfig.tiny())
    net.eval()
    loop = ServeLoop(net, ServeConfig(max_active=2, kv_blocks=32,
                                      block_size=16, max_seq_len=64))
    prompt = np.arange(1, 6, dtype=np.int64)
    loop.serve([prompt], max_new_tokens=3)            # traces both programs
    assert program_map.labels() == ["serve/decode", "serve/prefill/8"]
    noted = {k: program_map.scopes(k) for k in program_map.labels()}

    def never(*a, **k):
        raise AssertionError("the map was touched on a traced program")
    monkeypatch.setattr(program_map, "shapes", never)
    monkeypatch.setattr(program_map, "note", never)
    compiles = _backend_compiles(
        lambda: loop.serve([prompt + 1, prompt + 2], max_new_tokens=4))
    assert compiles == 0
    assert all(program_map.scopes(k) is v for k, v in noted.items())
    # eager use of the net still works: the first trace's rebinding of
    # the parameters was undone (and `note`'s lookup rebound nothing)
    assert np.isfinite(np.asarray(net(paddle.to_tensor(
        prompt[None])).numpy())).all()


def _net(kind):
    paddle.seed(0)
    net = {
        "gpt": lambda: GPT(GPTConfig.tiny()),
        "kimi": lambda: KimiK2(KimiK2Config.tiny(experts_held=(4, 8))),
        "longcat": lambda: LongCatFlash(
            LongCatFlashConfig.tiny(experts_held=(4, 8))),
        "hybrid": lambda: OlmoHybrid(OlmoHybridConfig.tiny()),
    }[kind]()
    net.eval()
    return net


@pytest.mark.parametrize("kind, must", [
    ("gpt", {"embed", "attn", "ffn", "head", "sample"}),
    ("kimi", {"embed", "attn", "ffn", "router", "experts", "head",
              "sample"}),
    ("longcat", {"embed", "attn", "ffn", "router", "experts", "head",
                 "sample"}),
    ("hybrid", {"embed", "attn", "linear_attn", "ffn", "head", "sample"}),
])
def test_each_decoder_names_its_work(kind, must):
    """The four served nets speak one vocabulary: the words a net must
    show are in its decode step and its prefill, every matrix product
    lies under a word inside a `layer{i}`, and the scopes that were there
    before (`sublayer{j}`, `zero_experts`, `gdn_step`, `gdn_chunk`) still
    are."""
    loop = ServeLoop(_net(kind), ServeConfig(
        max_active=2, kv_blocks=32, block_size=16, max_seq_len=64))
    loop.serve([np.arange(1, 12, dtype=np.int64)], max_new_tokens=3)
    for label in ("serve/decode", "serve/prefill/16"):
        paths = list(program_map.scopes(label)["ops"].values())
        seen = {program_map.scope_of(p) for p in paths}
        assert must <= seen, (label, must - seen)
        assert seen - {None} <= set(program_map.SCOPES)
        dots = [p for p in paths if p.endswith("/dot_general")]
        assert dots and all(program_map.scope_of(p) for p in dots), [
            p for p in dots if not program_map.scope_of(p)]
        layered = [p for p in dots if program_map.scope_of(p)
                   not in ("head", "embed", "sample")]
        assert all("/layer" in p for p in layered)
    kept = {"longcat": ("/sublayer0/", "/sublayer1/", "/zero_experts/"),
            "hybrid": ("/gdn_step/",)}.get(kind, ())
    decode = program_map.scopes("serve/decode")["ops"].values()
    for word in kept:
        assert any(word in p for p in decode), word
    if kind == "hybrid":
        assert any("/linear_attn/gdn_chunk/" in p for p in
                   program_map.scopes("serve/prefill/16")["ops"].values())


def test_dump_writes_what_another_process_reads(tmp_path):
    assert program_map.dump(str(tmp_path / "empty")) is None
    assert not (tmp_path / "empty").exists()
    jf = jax.jit(_toy)
    args = ({"w": jnp.ones((8, 8))}, jnp.ones((8, 8)), jnp.int32(2))
    shapes = program_map.shapes(args)
    jf(*args)
    program_map.note("toy", jf, shapes)
    # an operator's trace: the map lands beside the xplane file
    with profiler.xplane_trace(str(tmp_path)):
        jf(*args)[0].block_until_ready()
    path = tmp_path / program_map.FILE_NAME
    assert path.exists()
    with open(path) as f:
        said = json.load(f)
    assert said["scopes"] == list(program_map.SCOPES)
    assert said["programs"] == {"toy": program_map.scopes("toy")}
    assert os.path.getsize(path) < 1 << 20
