"""Inference export: jit.save -> StableHLO artifact -> Predictor round-trip
(reference CreatePaddlePredictor analysis_predictor.cc:1056,
save_inference_model fluid/io.py:1198)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.hapi.model import InputSpec


def _save_model(tmp_path):
    paddle.seed(9)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    prefix = os.path.join(str(tmp_path), "m")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], "float32", name="x")])
    return net, prefix


def test_save_writes_artifacts(tmp_path):
    _, prefix = _save_model(tmp_path)
    for suffix in (".pdmodel", ".pdiparams", ".stablehlo", ".pdinfer.json"):
        assert os.path.exists(prefix + suffix), suffix


def test_predictor_round_trip_in_process(tmp_path):
    net, prefix = _save_model(tmp_path)
    x = np.random.RandomState(0).randn(2, 8).astype("float32")
    want = np.asarray(net(paddle.to_tensor(x))._value)

    from paddle_tpu.inference import Config, create_predictor
    config = Config(prefix)
    pred = create_predictor(config)
    assert pred.get_input_names() == ["x"]
    h = pred.get_input_handle("x")
    h.copy_from_cpu(x)
    pred.run()
    got = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_predictor_symbolic_batch(tmp_path):
    """The exported artifact accepts batch sizes other than the example's."""
    net, prefix = _save_model(tmp_path)
    for b in (1, 3, 7):
        x = np.random.RandomState(b).randn(b, 8).astype("float32")
        want = np.asarray(net(paddle.to_tensor(x))._value)
        from paddle_tpu.inference import Predictor
        got = Predictor(prefix).run([x])[0]
        np.testing.assert_allclose(got, want, atol=1e-5)


def test_predictor_fresh_process_without_model_class(tmp_path):
    """The deployment check: a fresh interpreter that never sees the model's
    Python class (only paddle_tpu.inference) reproduces the outputs."""
    net, prefix = _save_model(tmp_path)
    x = np.random.RandomState(1).randn(4, 8).astype("float32")
    want = np.asarray(net(paddle.to_tensor(x))._value)
    xpath = os.path.join(str(tmp_path), "x.npy")
    opath = os.path.join(str(tmp_path), "out.npy")
    np.save(xpath, x)

    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from paddle_tpu.inference import Config, create_predictor\n"
        f"pred = create_predictor(Config({prefix!r}))\n"
        f"out = pred.run([np.load({xpath!r})])[0]\n"
        f"np.save({opath!r}, out)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd="/root/repo", capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = np.load(opath)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_predictor_fallback_without_stablehlo(tmp_path):
    net, prefix = _save_model(tmp_path)
    os.remove(prefix + ".stablehlo")
    x = np.random.RandomState(2).randn(2, 8).astype("float32")
    want = np.asarray(net(paddle.to_tensor(x))._value)
    from paddle_tpu.inference import Predictor
    got = Predictor(prefix).run([x])[0]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_no_phantom_submodules():
    """Every name in paddle_tpu._SUBMODULES must import (VERDICT r02 weak
    item 3: incubate/profiler/sysconfig/callbacks/inference were phantom)."""
    import paddle_tpu
    for name in paddle_tpu._SUBMODULES:
        mod = getattr(paddle_tpu, name)
        assert mod is not None, name


def test_incubate_functional_double_backward():
    from paddle_tpu.incubate import functional as IF
    f = lambda x: (x ** 3).sum()  # noqa: E731
    x = paddle.to_tensor(np.array([1.0, 2.0], dtype="float32"))
    g = IF.grad(f)(x)
    np.testing.assert_allclose(np.asarray(g._value), [3.0, 12.0], rtol=1e-6)
    h = IF.hessian(f)(x)
    np.testing.assert_allclose(np.asarray(h._value),
                               [[6.0, 0.0], [0.0, 12.0]], rtol=1e-6)


def test_static_save_inference_model_round_trip(tmp_path):
    """static.save_inference_model -> Predictor in a fresh process
    (reference fluid/io.py:1198 + CreatePaddlePredictor)."""
    import paddle_tpu.static as static
    from paddle_tpu import ops, optimizer

    paddle.enable_static()
    try:
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [2, 6], "float32")
            h = nn.Linear(6, 3)(x)
            y = ops.softmax(h, axis=-1)
        exe = static.Executor()
        exe.run(startup)
        xs = np.random.RandomState(0).randn(2, 6).astype("float32")
        want = exe.run(main, feed={"x": xs}, fetch_list=[y])[0]
        prefix = os.path.join(str(tmp_path), "static_m")
        static.save_inference_model(prefix, [x], [y], exe)

        # round trip through load_inference_model
        prog2, feeds, fetches = static.load_inference_model(prefix)
        got = exe.run(prog2, feed={"x": xs}, fetch_list=fetches)[0]
        np.testing.assert_allclose(got, want, atol=1e-6)
    finally:
        paddle.disable_static()

    # fresh process via the Predictor over the StableHLO artifact
    opath = os.path.join(str(tmp_path), "o.npy")
    xpath = os.path.join(str(tmp_path), "x.npy")
    np.save(xpath, xs)
    code = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from paddle_tpu.inference import Predictor\n"
        f"out = Predictor({prefix!r}).run([np.load({xpath!r})])[0]\n"
        f"np.save({opath!r}, out)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd="/root/repo", capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    np.testing.assert_allclose(np.load(opath), want, atol=1e-5)


def test_encrypted_model_round_trip(tmp_path):
    """N35 analog: AES-256-GCM model encryption at rest; wrong/missing key
    fails loudly, right key reproduces outputs."""
    from paddle_tpu.framework.crypto import Cipher, CipherUtils
    from paddle_tpu.inference import Config, Predictor, encrypt_model

    net, prefix = _save_model(tmp_path)
    x = np.random.RandomState(3).randn(2, 8).astype("float32")
    want = np.asarray(net(paddle.to_tensor(x))._value)

    key = CipherUtils.gen_key_to_file(os.path.join(str(tmp_path), "k"))
    encrypt_model(prefix, key)
    assert not os.path.exists(prefix + ".stablehlo")
    assert os.path.exists(prefix + ".stablehlo.enc")

    with pytest.raises(PermissionError, match="encrypted"):
        Predictor(prefix)  # no key -> loud

    cfg = Config(prefix)
    cfg.set_cipher_key(key)
    got = Predictor(cfg).run([x])[0]
    np.testing.assert_allclose(got, want, atol=1e-5)

    bad = Config(prefix)
    bad.set_cipher_key(CipherUtils.gen_key())
    with pytest.raises(Exception):  # authentication failure
        Predictor(bad)

    # raw cipher surface
    c = Cipher(key)
    blob = c.encrypt(b"secret weights")
    assert c.decrypt(blob) == b"secret weights"
    with pytest.raises(Exception):
        c.decrypt(blob[:-1] + bytes([blob[-1] ^ 1]))  # tamper detected


def test_resnet18_trains_tiny():
    """BASELINE config 2 representative: ResNet forward/backward/step."""
    from paddle_tpu.vision.models import resnet18
    from paddle_tpu import optimizer
    paddle.seed(0)
    net = resnet18(num_classes=4)
    opt = optimizer.Momentum(learning_rate=0.01,
                             parameters=net.parameters())
    x = paddle.to_tensor(np.random.RandomState(0).rand(
        2, 3, 32, 32).astype("float32"))
    y = paddle.to_tensor(np.array([0, 3], "int64"))
    ce = nn.CrossEntropyLoss()
    losses = []
    for _ in range(3):
        loss = ce(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
