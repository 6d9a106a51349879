"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up re-design of the reference PaddlePaddle (~v2.0-rc) for TPU:
the user API keeps the reference's shape (`paddle.*` tensor functions,
`nn.Layer`, `optimizer`, `Model.fit`, `paddle.static`, `paddle.distributed`/
fleet), while the execution model is XLA-first — eager ops are jnp kernels,
training steps are traced once and compiled (jit/pjit), parallelism is mesh
sharding + compiler-inserted ICI collectives instead of NCCL rings.
See /root/repo/SURVEY.md for the layer-by-layer mapping to the reference.
"""
from __future__ import annotations

__version__ = "0.1.0"

import jax as _jax

# Paddle semantics: int64 indices/labels are first-class. Enable x64 so they
# survive; float tensors still default to float32 (core/tensor._coerce), and
# the compute path prefers bf16 on the MXU (ops/linalg.py).
_jax.config.update("jax_enable_x64", True)

# Persistent compilation cache, placeable from outside: where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and nothing here
# names another directory; otherwise a fixed path beside the package (the
# path is part of the cache key, so a directory that moves never hits).
import os as _os

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache"))

from .core.dtype import (bfloat16, bool_, complex128, complex64, float16,  # noqa: F401
                         float32, float64, int16, int32, int64, int8, uint8)
from .core.dtype import bool_ as bool  # noqa: F401,A001
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.rng import seed  # noqa: F401
from .core.tensor import Tensor, to_tensor  # noqa: F401
from .core.tape import (no_grad, enable_grad, is_grad_enabled,  # noqa: F401
                        set_grad_enabled, grad)

from .ops import *  # noqa: F401,F403  — paddle.* tensor functions
from . import ops  # noqa: F401

from . import autograd  # noqa: F401
from . import device  # noqa: F401
from .device import (CPUPlace, CUDAPlace, TPUPlace, get_device,  # noqa: F401
                     set_device, is_compiled_with_cuda)

# flight recorder: arm the fatal-signal dump hook when a dump dir is
# configured (PADDLE_TPU_DUMP_DIR); a pure no-op otherwise
from .core import flight_recorder as _flight_recorder
_flight_recorder.maybe_install()


def in_dynamic_mode():
    try:
        from . import static as _static
    except ImportError:
        return True
    return not _static.in_static_mode()


def enable_static():
    from . import static as _static
    _static.enable_static_()


def disable_static():
    try:
        from . import static as _static
    except ImportError:
        return
    _static.disable_static_()


def disable_signal_handler():  # parity no-op
    pass


# Subpackages are importable lazily (paddle.nn, paddle.optimizer, ...) so the
# core stays importable while higher layers are under construction.
import importlib as _importlib

_SUBMODULES = ("nn", "optimizer", "metric", "io", "amp", "static",
               "distributed", "vision", "jit", "hapi", "incubate",
               "profiler", "text", "sysconfig", "callbacks", "inference",
               "framework", "regularizer", "memory", "quantization",
               "distribution", "version", "utils", "fluid", "reader",
               "dataset", "onnx", "tensor")


from ._legacy_api import *  # noqa: F401,F403  — v1/compat root names
from ._legacy_api import VarBase, LoDTensor, LoDTensorArray  # noqa: F401

# Lazily-injected non-module names (see __getattr__); enumerated so the
# API.spec snapshot is deterministic regardless of import order.
__all_lazy__ = ("Model", "summary", "flops", "save", "load", "batch")


def __getattr__(name):
    if name in _SUBMODULES:
        mod = _importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name == "Model":
        from .hapi import Model
        globals()["Model"] = Model
        return Model
    if name in ("summary", "flops"):
        from .hapi.summary import flops, summary
        globals().update(summary=summary, flops=flops)
        return globals()[name]
    if name in ("save", "load"):
        from .framework.io import load, save
        globals().update(save=save, load=load)
        return globals()[name]
    if name == "batch":
        from .reader import batch
        globals()["batch"] = batch
        return batch
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
