"""A configuration file -> the model it names. The file keeps the source's
own keys at top level; `model.config_kwargs` maps the repo's constructor
arguments onto them ("@n_embd" reads the top-level key `n_embd`)."""
from __future__ import annotations

import importlib


def load_object(dotted):
    """"package.module.Name" -> the object."""
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


def resolve(value, config):
    """"@key" -> config[key]; containers are resolved leaf by leaf."""
    if isinstance(value, str) and value.startswith("@"):
        return config[value[1:]]
    if isinstance(value, dict):
        return {k: resolve(v, config) for k, v in value.items()}
    if isinstance(value, list):
        return [resolve(v, config) for v in value]
    return value


def model_kwargs(config):
    return resolve(config["model"]["config_kwargs"], config)


def build_net(config, seed):
    """The seeded network of `config` (weights from `paddle.seed(seed)`,
    initialised by the model's own constructor, as users get them)."""
    import paddle_tpu as paddle
    paddle.seed(seed)
    cfg = load_object(config["model"]["config_class"])(**model_kwargs(config))
    return load_object(config["model"]["class"])(cfg)
