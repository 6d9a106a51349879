"""Versioned model serialization (VERDICT r04 item 4).

Reference analogs: framework/framework.proto:186 (op version map),
framework/save_load_util.cc (versioned headers). The format is JSON+npz
with ops referenced by registry name + version — no pickled qualnames, so
internal module renames cannot break saved models."""
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, nn, ops
from paddle_tpu.framework.program_serde import (FORMAT_VERSION,
                                                OpVersionError,
                                                load_program)


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(4, 8)
        self.fc2 = nn.Linear(8, 2)

    def forward(self, x):
        return self.fc2(ops.relu(self.fc1(x)))


def _save(net, tmp, name="m"):
    path = os.path.join(tmp, name)
    jit.save(net, path, input_spec=[jit.InputSpec([2, 4], "float32", "x")])
    return path


def test_pdmodel_is_json_schema_without_qualnames():
    paddle.seed(0)
    net = SmallNet()
    net.eval()
    with tempfile.TemporaryDirectory() as tmp:
        path = _save(net, tmp)
        raw = open(path + ".pdmodel", "rb").read()
        doc = json.loads(raw)  # JSON, not pickle
        assert doc["format_version"] == FORMAT_VERSION
        assert doc["op_versions"]  # version map recorded
        # nothing in the document resolves by module path: a rename of
        # paddle_tpu internals cannot invalidate the artifact
        assert b"paddle_tpu.ops" not in raw
        assert b"__module__" not in raw
        assert os.path.exists(path + ".pdmodel.npz")


def test_save_load_numeric_roundtrip():
    paddle.seed(0)
    net = SmallNet()
    net.eval()
    x = np.random.RandomState(0).randn(2, 4).astype("float32")
    want = net(paddle.to_tensor(x)).numpy()
    with tempfile.TemporaryDirectory() as tmp:
        path = _save(net, tmp)
        loaded = jit.load(path)
        np.testing.assert_allclose(loaded(paddle.to_tensor(x)).numpy(),
                                   want, rtol=1e-5, atol=1e-6)


def test_fresh_process_load_after_module_rename_simulation():
    """The 'rename an internal module' criterion: the loader process
    imports paddle_tpu with an alias shim in place of a renamed module
    path; since the artifact stores registry names only, it loads."""
    paddle.seed(0)
    net = SmallNet()
    net.eval()
    x = np.random.RandomState(1).randn(2, 4).astype("float32")
    want = net(paddle.to_tensor(x)).numpy()
    with tempfile.TemporaryDirectory() as tmp:
        path = _save(net, tmp)
        np.save(os.path.join(tmp, "x.npy"), x)
        np.save(os.path.join(tmp, "want.npy"), want)
        code = f"""
import sys
sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})
import numpy as np
import paddle_tpu as paddle
# simulate an internal refactor: the activation module moves; loading
# must not care because ops resolve via OP_REGISTRY, not module paths
import paddle_tpu.ops.activation as act
sys.modules["paddle_tpu.ops.activation_renamed"] = act
del sys.modules["paddle_tpu.ops.activation"]
from paddle_tpu import jit
loaded = jit.load({path!r})
x = np.load({os.path.join(tmp, "x.npy")!r})
want = np.load({os.path.join(tmp, "want.npy")!r})
got = loaded(paddle.to_tensor(x)).numpy()
np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
print("RENAMED-LOAD-OK")
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert "RENAMED-LOAD-OK" in r.stdout, r.stdout + r.stderr


def test_op_version_gate():
    paddle.seed(0)
    net = SmallNet()
    net.eval()
    with tempfile.TemporaryDirectory() as tmp:
        path = _save(net, tmp)
        doc = json.load(open(path + ".pdmodel"))
        # simulate an artifact produced by a FUTURE framework whose matmul
        # op was bumped to version 99
        bumped = False
        for op in doc["ops"]:
            if op["fn"].get("__opreg__") == "matmul":
                op["fn"]["version"] = 99
                bumped = True
        assert bumped
        doc["op_versions"]["matmul"] = 99
        json.dump(doc, open(path + ".pdmodel", "w"))
        with pytest.raises(OpVersionError, match="version 99"):
            load_program(path)

        # a future FORMAT version is refused outright
        doc["format_version"] = FORMAT_VERSION + 1
        json.dump(doc, open(path + ".pdmodel", "w"))
        with pytest.raises(OpVersionError, match="format_version"):
            load_program(path)


def test_control_flow_program_serializes_structurally():
    from paddle_tpu.jit.dy2static import convert_layer

    class CondNet(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x):
            h = self.fc(x)
            if h.mean() > 0:
                h = ops.relu(h)
            else:
                h = h * 0.5
            i = 0
            while i < 2:
                h = h + 0.25
                i += 1
            return h

    paddle.seed(0)
    net = CondNet()
    net.eval()
    xs = [np.random.RandomState(0).randn(2, 4).astype("float32"),
          -np.abs(np.random.RandomState(1).randn(2, 4)).astype("float32")]
    want = [net(paddle.to_tensor(x)).numpy() for x in xs]
    with tempfile.TemporaryDirectory() as tmp:
        path = _save(net, tmp, "cond")
        doc = json.load(open(path + ".pdmodel"))
        kinds = {next(iter(op["fn"])) for op in doc["ops"]}
        assert "__cond__" in kinds or "__while__" in kinds
        loaded = jit.load(path)
        for x, w in zip(xs, want):
            np.testing.assert_allclose(loaded(paddle.to_tensor(x)).numpy(),
                                       w, rtol=1e-5, atol=1e-6)


def test_legacy_pickle_still_loads():
    import pickle
    paddle.seed(0)
    net = SmallNet()
    net.eval()
    x = np.random.RandomState(2).randn(2, 4).astype("float32")
    want = net(paddle.to_tensor(x)).numpy()
    with tempfile.TemporaryDirectory() as tmp:
        path = _save(net, tmp)
        loaded_prog, feeds = load_program(path)
        # rewrite as a legacy pickle artifact and load through jit.load
        with open(path + ".pdmodel", "wb") as f:
            pickle.dump({"program": loaded_prog, "feed_names": feeds}, f)
        os.remove(path + ".pdmodel.npz")
        loaded = jit.load(path)
        np.testing.assert_allclose(loaded(paddle.to_tensor(x)).numpy(),
                                   want, rtol=1e-5, atol=1e-6)


class HashNet(nn.Layer):
    def forward(self, x):
        return ops.hash_bucket(x, num_hash=2, mod_by=97)


def test_hash_bucket_v2_version_gate():
    """ADVICE r05: hash_bucket v2 fixed the negative-bucket wraparound;
    artifacts record the bumped version so a v1 framework refuses them
    (and this build accepts old v1 artifacts, whose semantics it
    supersedes compatibly for non-wrapping ids)."""
    net = HashNet()
    net.eval()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "h")
        jit.save(net, path, input_spec=[jit.InputSpec([2, 1], "int64", "x")])
        doc = json.load(open(path + ".pdmodel"))
        assert doc["op_versions"]["hash_bucket"] == 2
        # an artifact from a FUTURE v3 framework is refused
        for op in doc["ops"]:
            if op["fn"].get("__opreg__") == "hash_bucket":
                op["fn"]["version"] = 3
        doc["op_versions"]["hash_bucket"] = 3
        json.dump(doc, open(path + ".pdmodel", "w"))
        with pytest.raises(OpVersionError, match="hash_bucket.*version 3"):
            load_program(path)
        # an OLD v1 artifact still loads (forward compatibility)
        for op in doc["ops"]:
            if op["fn"].get("__opreg__") == "hash_bucket":
                op["fn"]["version"] = 1
        doc["op_versions"]["hash_bucket"] = 1
        json.dump(doc, open(path + ".pdmodel", "w"))
        prog, feeds = load_program(path)
        assert any(op.name == "hash_bucket" for op in prog.ops)
