"""No silent demotion: a Pallas kernel whose gate admitted the call runs
or raises at every dispatch site — an injected failure must reach the
caller, never a jnp result with a warning (a demoted run measures a
different program than it meant to and still exits 0)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core import monitor


@pytest.fixture
def interpret():
    paddle.set_flags({"FLAGS_pallas_interpret": True,
                      "FLAGS_flash_min_seq": 0})
    yield
    paddle.set_flags({"FLAGS_pallas_interpret": False,
                      "FLAGS_flash_min_seq": 1024})


def _boom(*a, **k):
    raise RuntimeError("injected Mosaic crash")


def test_flash_crash_propagates(interpret, monkeypatch):
    monkeypatch.setattr(F, "_flash_sdpa", _boom)
    monitor.reset(prefix="pallas.")
    rng = np.random.RandomState(0)
    mk = lambda *s: paddle.to_tensor(  # noqa: E731
        rng.randn(*s).astype("float32"))
    with pytest.raises(RuntimeError, match="injected"):
        F.scaled_dot_product_attention(mk(2, 2, 32, 16), mk(2, 2, 32, 16),
                                       mk(2, 2, 32, 16))
    assert monitor.stat_get("pallas.hit.flash_attention") == 0
    assert not monitor.stats("pallas.fallback.")


def test_fused_ce_crash_propagates(interpret, monkeypatch):
    monkeypatch.setattr(F, "_fused_ce_op", _boom)
    rng = np.random.RandomState(1)
    h = paddle.to_tensor(rng.randn(16, 8).astype("float32"))
    w = paddle.to_tensor(rng.randn(50, 8).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 50, 16).astype("int64"))
    with pytest.raises(RuntimeError, match="injected"):
        F.fused_linear_cross_entropy(h, w, None, y)


def test_generate_raises_under_decode_crash(interpret, monkeypatch):
    """The decode scenario end to end: a dead decode kernel fails the
    whole generation (scan included) instead of finishing on jnp."""
    import paddle_tpu.ops.pallas as pallas_pkg
    from paddle_tpu.text.models.gpt import GPT, GPTConfig

    paddle.seed(0)
    net = GPT(GPTConfig.tiny())
    net.eval()
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 1024, (2, 5)).astype("int64"))
    monkeypatch.setattr(pallas_pkg, "decode_attention", _boom)
    with pytest.raises(RuntimeError, match="injected"):
        net.generate(ids, max_new_tokens=6, temperature=0, use_cache=True)
