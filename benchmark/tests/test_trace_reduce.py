"""The reduction from trace events to numbers, on hand-made events and on
the small recorded traces kept beside this file (tests/data/, cut from TPU
v5e runs of PR 23 and, with both serve kernels, PR 28: what `device_lines`
returned, as JSON)."""
import json
import os

import pytest

from benchmark import run as runner
from benchmark.layer_metrics import paged_attn_share
from benchmark.lib import trace_reduce as tr
from benchmark.tests.toy import BENCH_DIR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
EVENTS = [["fusion.1", 0, 100], ["all-reduce.3", 50, 100],
          ["fusion.2", 200, 50], ["all-reduce.4", 300, 20],
          ["custom-call.7", 400, 100]]


def test_busy_is_the_union_and_clips():
    assert tr.busy_s(EVENTS) == pytest.approx(320e-9)
    assert tr.span_s(EVENTS) == pytest.approx(500e-9)
    assert tr.busy_s(EVENTS, t0_ns=100, t1_ns=310) == pytest.approx(110e-9)
    assert tr.busy_s([]) == 0.0 and tr.span_s([]) == 0.0


def test_top_ops_group_by_stem_and_gaps_are_ranked():
    top = dict(tr.top_ops(EVENTS, 10))
    assert top["fusion"] == pytest.approx(150e-9)
    assert top["all-reduce"] == pytest.approx(120e-9)
    assert tr.top_ops(EVENTS, 1)[0][0] == "fusion"
    gaps = tr.idle_gaps(EVENTS, 2)
    assert [round(s * 1e9) for _, s in gaps] == [80, 50]
    assert gaps[0][0].startswith("host span: not available")
    assert "after all-reduce, before custom-call" in gaps[0][0]


def kernel_share(ops, pattern, kv_write=None):
    """The paged_attn_share reader on one device's op list, as a share."""
    patterns = {"paged_attn": pattern, "kv_write": kv_write}
    got = paged_attn_share.read({"kernel_patterns": patterns,
                                 "trace_ops": {0: ops}})
    return None if got is None else got / 100.0


def test_kernel_share_and_exposed_collectives(capsys):
    assert kernel_share(EVENTS, r"^custom-call") == pytest.approx(100 / 320)
    assert kernel_share(EVENTS, r"^no-such-op") is None
    assert paged_attn_share.read({"trace_ops": {0: EVENTS}}) is None
    # the KV writer is a kernel of its own, under a pattern of its own: its
    # seconds are printed, the share is the attention kernel's alone; a
    # trace with nothing but the writer reports nothing
    kernel = "custom-call[tpu_custom_call] "
    both = EVENTS + [[kernel + "_paged_call_once.3", 600, 60],
                     [kernel + "_paged_write_once.5", 700, 20]]
    capsys.readouterr()
    assert kernel_share(both, "_paged_call_once") == pytest.approx(60 / 400)
    assert capsys.readouterr().out == (
        "trace: kernel seconds: paged attention 0.0000, of 0.0000 busy\n")
    assert kernel_share(both, "_paged_call_once", "_paged_write_once") == (
        pytest.approx(60 / 400))
    assert capsys.readouterr().out == (
        "trace: kernel seconds: paged attention 0.0000, KV writer "
        "(_paged_write_once) 0.0000, of 0.0000 busy\n")
    assert kernel_share(EVENTS[:2] + both[-1:], "_paged_call_once",
                        "_paged_write_once") is None
    # all-reduce.3 runs alone for 50 ns, all-reduce.4 for all its 20 ns
    assert tr.exposed_collective_s(EVENTS) == pytest.approx(70e-9)
    assert tr.exposed_collective_s(EVENTS[:1]) is None


def test_short_name_cuts_the_hlo_text_the_profiler_prints():
    kernel = ('%decode_step.48 = bf16[32,25,8,64]{3,2,1,0:T(8,128)(2,1)S(1)} '
              'custom-call(s32[32]{0:T(128)S(1)} %copy-done.490, '
              'bf16[225,25,128,64]{3,2,1,0:T(8,128)(2,1)} %copy.730), '
              'custom_call_target="tpu_custom_call", operand_layout_'
              'constraints={s32[32]{0}}')
    fusion = ('%fusion.12 = (bf16[768]{0:T(1024)(128)(2,1)}, f32[128,128]'
              '{1,0:T(8,128)S(1)}) fusion(bf16[128,128]{1,0} %x), kind=kLoop')
    done = ('%all-reduce-done.3 = f32[10]{0} all-reduce-done((f32[10]{0}, '
            'f32[10]{0}) %all-reduce-start.3)')
    assert tr.short_name(kernel) == (
        "custom-call[tpu_custom_call] decode_step.48")
    assert tr.op_stem(tr.short_name(kernel)) == (
        "custom-call[tpu_custom_call] decode_step")
    assert tr.short_name(fusion) == "fusion fusion.12"
    assert tr.COLLECTIVE.match(tr.short_name(done))
    assert not tr.COLLECTIVE.match(tr.short_name(fusion))
    assert tr.short_name("jit_decode_step(123)") == "jit_decode_step(123)"


def test_xplane_of_a_cpu_run_has_no_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    from benchmark.lib import profiler
    profiler.start(str(tmp_path))
    jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))).block_until_ready()
    profiler.stop()
    path = tr.find_xplane(str(tmp_path))
    assert path
    # the CPU has no /device:TPU plane
    assert tr.device_lines(path) == {tr.OPS_LINE: {}}


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(DATA) if f.endswith(".json")))
def test_recorded_trace(name, capsys):
    with open(os.path.join(DATA, name + ".json")) as f:
        rec = json.load(f)
    ops = rec["ops"]
    assert tr.busy_s(ops) == pytest.approx(rec["expect"]["busy_s"])
    assert tr.span_s(ops) == pytest.approx(rec["expect"]["span_s"])
    assert 0 < tr.busy_s(ops) <= tr.span_s(ops)
    assert tr.top_ops(ops, 3)[0][0] == rec["expect"]["top_stem"]
    shares = rec["expect"].get("shares", {})
    if "kernel_seconds_line" in rec["expect"]:
        # both kernels, recorded (PR 28) when one pattern matched every
        # custom call and the reader left the writer out by name in code:
        # the two patterns of configs/gpt2_xl.json read the same share, to
        # the last digit, and print the same line
        named = runner.load_json(BENCH_DIR, "configs", "gpt2_xl.json")[
            "kernel_patterns"]
        (share,) = shares.values()
        assert kernel_share(ops, named["paged_attn"],
                            named["kv_write"]) == share
        assert capsys.readouterr().out.endswith(
            rec["expect"]["kernel_seconds_line"])
    else:
        for pattern, share in shares.items():
            assert kernel_share(ops, pattern) == pytest.approx(share)
    if "exposed_collective_s" in rec["expect"]:
        assert tr.exposed_collective_s(ops) == pytest.approx(
            rec["expect"]["exposed_collective_s"])
