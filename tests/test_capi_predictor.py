"""C-ABI predictor (VERDICT r03 item 9 / N32 client story; reference
inference/capi/, go/paddle/predictor.go): build libpaddle_tpu_capi.so,
compile a real C client against the public header, run it in a fresh
process over a jit.save artifact, and check its output matches the
in-process Python Predictor bit for bit (f32)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = tmp_path_factory.mktemp("capi_model")
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    net.eval()
    prefix = str(d / "model")
    from paddle_tpu import jit
    from paddle_tpu.hapi.model import InputSpec
    jit.save(net, prefix, input_spec=[InputSpec([2, 4], "float32")])
    x = np.random.RandomState(0).randn(2, 4).astype("float32")
    from paddle_tpu import inference
    pred = inference.create_predictor(inference.Config(prefix))
    (ref,) = pred.run([x])
    return prefix, x, ref


def test_capi_from_c_client(artifact, tmp_path):
    prefix, x, ref = artifact
    from paddle_tpu._native import build_capi, capi_header
    so = build_capi()

    c_src = textwrap.dedent(r"""
        #include <stdio.h>
        #include <stdlib.h>
        #include "paddle_tpu_capi.h"

        int main(int argc, char** argv) {
            PD_Predictor* p = PD_NewPredictor(argv[1], "");
            if (!p) { fprintf(stderr, "create: %s\n", PD_GetLastError());
                      return 2; }
            float in[8];
            FILE* f = fopen(argv[2], "rb");
            if (fread(in, sizeof(float), 8, f) != 8) return 3;
            fclose(f);
            const void* bufs[1] = {in};
            int dtypes[1] = {PD_DTYPE_FLOAT32};
            int64_t shape[2] = {2, 4};
            const int64_t* shapes[1] = {shape};
            int ndims[1] = {2};
            if (PD_PredictorRun(p, bufs, dtypes, shapes, ndims, 1)) {
                fprintf(stderr, "run: %s\n", PD_GetLastError());
                return 4;
            }
            int n = PD_PredictorNumOutputs(p);
            printf("%d\n", n);
            for (int i = 0; i < n; i++) {
                const float* data; const int64_t* oshape; int ondim;
                PD_PredictorOutput(p, i, &data, &oshape, &ondim);
                long long numel = 1;
                for (int d = 0; d < ondim; d++) {
                    printf("%lld ", (long long)oshape[d]);
                    numel *= oshape[d];
                }
                printf("\n");
                for (long long k = 0; k < numel; k++)
                    printf("%.9g\n", data[k]);
            }
            PD_DeletePredictor(p);
            return 0;
        }
    """)
    csrc = tmp_path / "client.c"
    csrc.write_text(c_src)
    exe = tmp_path / "client"
    import sysconfig
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION")
    cmd = ["gcc", "-O1", str(csrc), "-o", str(exe),
           f"-I{os.path.dirname(capi_header())}", so,
           f"-Wl,-rpath,{os.path.dirname(so)}"]
    if libdir:
        cmd += [f"-L{libdir}", f"-Wl,-rpath,{libdir}"]
    cmd += [f"-lpython{ver}", "-ldl", "-lm"]
    subprocess.run(cmd, check=True, capture_output=True)

    xfile = tmp_path / "x.bin"
    xfile.write_bytes(np.ascontiguousarray(x).tobytes())
    env = {**os.environ, "PYTHONPATH": f"{os.environ.get('PYTHONPATH', '')}"
           f":{REPO}", "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([str(exe), prefix, str(xfile)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"C client failed: {r.stderr}\n{r.stdout}"
    lines = r.stdout.split()
    n = int(lines[0])
    assert n == 1
    shape = (int(lines[1]), int(lines[2]))
    vals = np.array([float(v) for v in lines[3:3 + shape[0] * shape[1]]],
                    np.float32).reshape(shape)
    np.testing.assert_allclose(vals, ref, rtol=1e-6, atol=1e-7)


def test_capi_reports_errors(tmp_path):
    """Bad model prefix surfaces through PD_GetLastError, not a crash."""
    import ctypes

    from paddle_tpu._native import build_capi
    lib = ctypes.CDLL(build_capi())
    lib.PD_NewPredictor.restype = ctypes.c_void_p
    lib.PD_NewPredictor.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.PD_GetLastError.restype = ctypes.c_char_p
    h = lib.PD_NewPredictor(str(tmp_path / "nope").encode(), b"")
    assert not h
    assert b"pdinfer" in lib.PD_GetLastError() or \
        b"not found" in lib.PD_GetLastError()


# ---- C train API (N33; reference train/demo/demo_trainer.cc) -------------

def test_capi_trainer_from_c_client(tmp_path):
    """A real C host trains the linear-regression program: loss must
    decrease across steps and params must persist."""
    from paddle_tpu import static, optimizer
    paddle.enable_static()
    main = static.Program("capi_train")
    with static.program_guard(main):
        x = static.data("x", [-1, 3], "float32")
        y = static.data("y", [-1, 1], "float32")
        net = nn.Linear(3, 1, bias_attr=False)
        loss = paddle.ops.mse_loss(net(x), y)
        optimizer.SGD(learning_rate=0.1).minimize(loss)
    paddle.disable_static()

    from paddle_tpu.static import capi_train
    art = str(tmp_path / "train.pdprog")
    capi_train.save_train_program(main, art)

    rng = np.random.RandomState(0)
    X = rng.rand(64, 3).astype("float32")
    W = rng.randn(3, 1).astype("float32")
    Y = X @ W
    (tmp_path / "x.bin").write_bytes(X.tobytes())
    (tmp_path / "y.bin").write_bytes(Y.tobytes())

    from paddle_tpu._native import build_capi, capi_header
    so = build_capi()
    c_src = textwrap.dedent(r"""
        #include <stdio.h>
        #include <stdlib.h>
        #include "paddle_tpu_capi.h"

        int main(int argc, char** argv) {
            PD_Trainer* t = PD_NewTrainer(argv[1]);
            if (!t) { fprintf(stderr, "new: %s\n", PD_GetLastError());
                      return 2; }
            static float X[64*3], Y[64];
            FILE* f = fopen(argv[2], "rb");
            if (fread(X, 4, 64*3, f) != 64*3) return 3;
            fclose(f);
            f = fopen(argv[3], "rb");
            if (fread(Y, 4, 64, f) != 64) return 3;
            fclose(f);
            const void* bufs[2] = {X, Y};
            int dtypes[2] = {PD_DTYPE_FLOAT32, PD_DTYPE_FLOAT32};
            int64_t sx[2] = {64, 3}, sy[2] = {64, 1};
            const int64_t* shapes[2] = {sx, sy};
            int ndims[2] = {2, 2};
            float first = 0, last = 0;
            for (int i = 0; i < 400; i++) {
                float loss;
                if (PD_TrainerRunStep(t, bufs, dtypes, shapes, ndims, 2,
                                      &loss)) {
                    fprintf(stderr, "step: %s\n", PD_GetLastError());
                    return 4;
                }
                if (i == 0) first = loss;
                last = loss;
            }
            printf("%.9g %.9g\n", first, last);
            if (PD_TrainerSave(t, argv[4])) {
                fprintf(stderr, "save: %s\n", PD_GetLastError());
                return 5;
            }
            PD_DeleteTrainer(t);
            return 0;
        }
    """)
    csrc = tmp_path / "train_client.c"
    csrc.write_text(c_src)
    exe = tmp_path / "train_client"
    import sysconfig
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION")
    cmd = ["gcc", "-O1", str(csrc), "-o", str(exe),
           f"-I{os.path.dirname(capi_header())}", so,
           f"-Wl,-rpath,{os.path.dirname(so)}"]
    if libdir:
        cmd += [f"-L{libdir}", f"-Wl,-rpath,{libdir}"]
    cmd += [f"-lpython{ver}", "-ldl", "-lm"]
    subprocess.run(cmd, check=True, capture_output=True)

    env = {**os.environ, "PYTHONPATH": f"{os.environ.get('PYTHONPATH', '')}"
           f":{REPO}", "JAX_PLATFORMS": "cpu"}
    out_params = str(tmp_path / "trained")
    r = subprocess.run(
        [str(exe), art, str(tmp_path / "x.bin"), str(tmp_path / "y.bin"),
         out_params], env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"C trainer failed: {r.stderr}\n{r.stdout}"
    first, last = (float(v) for v in r.stdout.split())
    assert last < first * 0.05, (first, last)
    # saved params load back and are near the true W
    from paddle_tpu.framework.io import load as fload
    state = fload(out_params + ".pdparams")
    w = next(iter(state.values()))
    np.testing.assert_allclose(np.asarray(w), W, atol=0.25)
