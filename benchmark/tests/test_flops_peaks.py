"""The FLOP function and the peak table."""
import pytest

from benchmark.lib import flops, peaks
from benchmark.lib.build import model_kwargs
from benchmark.tests import toy


def test_bert_base_flops_per_token_at_s128():
    kw = model_kwargs(toy.load("configs", "bert_base_mlm"))
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 768 + 30522 * 768
    assert n == pytest.approx(108.96e6, rel=1e-3)
    got = flops.train_flops_per_token(
        "paddle_tpu.text.models.bert.Bert", kw, 128)
    assert got == 6 * n + 12 * 12 * 768 * 128
    assert got == pytest.approx(667.9e6, rel=1e-3)


def test_unknown_device_or_model_is_an_error():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops_per_s")
    with pytest.raises(KeyError):
        flops.train_flops_per_token("no.such.Model", {}, 128)
