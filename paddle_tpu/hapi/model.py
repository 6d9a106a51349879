"""High-level Model API.

Analog of reference python/paddle/hapi/model.py (Model :808, fit :1296,
prepare :1241, StaticGraphAdapter :223 / DynamicGraphAdapter :608).

Design delta (SURVEY.md §7.3): the two adapters collapse into ONE compiled
engine. The layer graph is traced functionally — parameters, buffers and
optimizer slots become pytree inputs/outputs of a pure step function that
jax.jit compiles to a single XLA program (forward + backward + optimizer
fused; buffers donated). That one program per (mode, shapes) replaces both
the static Executor program and the dygraph per-op path. Sharding hooks:
when paddle_tpu.distributed configured a mesh + sharding rules, the same
step is pjit-partitioned (engine consults distributed.sharding).
"""
from __future__ import annotations

import contextlib
import os
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as _rng
from ..core import tape as _tape
from ..core.tensor import Tensor
from ..metric import Metric
from ..nn.layer.layers import Layer
from ..framework.io import load as _load, save as _save
from .callbacks import config_callbacks

__all__ = ["Model", "InputSpec"]


_NO_BATCH = object()   # what next() gives once the loader is exhausted


class _LazyLoss:
    """`logs["loss"]` placeholder in the async fit loop
    (docs/async_executor.md): materializes the EXACT loss of its own step
    on first read (float()/format()/np.asarray), draining the window in
    submission order so an in-flight failure names the first failing
    step. A callback that consumes the loss every batch (e.g. VisualDL's
    add_scalar) therefore sees exact per-batch values at per-batch sync
    cost; a loop where nothing reads it keeps the pipeline."""

    __slots__ = ("step", "_lval", "_drain", "_val")

    def __init__(self, step, lval, drain):
        self.step = step
        self._lval = lval
        self._drain = drain
        self._val = None

    def _materialize(self):
        """Called by the window drain, in submission order."""
        if self._val is None:
            try:
                self._val = float(np.asarray(self._lval))
            except Exception as e:
                raise RuntimeError(
                    f"hapi pipelined step {self.step} failed: "
                    f"{type(e).__name__}: {e}") from e
            self._lval = None
        return self._val

    def value(self):
        if self._val is None:
            self._drain(self.step)  # in-order: names the first failure
        return self._val if self._val is not None else self._materialize()

    def __float__(self):
        return self.value()

    def __format__(self, spec):
        return format(self.value(), spec)

    def __repr__(self):
        return repr(self.value())

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(self.value())
        return arr.astype(dtype) if dtype is not None else arr


class InputSpec:
    """Shape/dtype declaration (reference paddle/static/input.py InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _to_raw(x):
    if isinstance(x, Tensor):
        return x._value
    return jnp.asarray(x)


class _CompiledEngine:
    """Traces net+loss+optimizer into pure jitted step functions."""

    def __init__(self, model):
        self.model = model
        self._train_fn = None
        self._eval_fn = None
        self._pred_fn = None
        self._grad_fn = None
        self._apply_fn = None
        self._accum_grads = None
        self._accum_count = 0
        self._param_names = None
        self._localsgd = None         # replicated-state LocalSGD mode

    # ---- functional pieces -------------------------------------------------
    def _amp_ctx(self):
        import contextlib
        cfg = self.model._amp_configs
        if not cfg:
            return contextlib.nullcontext()
        from .. import amp as amp_mod
        return amp_mod.auto_cast(
            level=cfg["level"], dtype=cfg["dtype"],
            custom_white_list=cfg.get("custom_white_list"),
            custom_black_list=cfg.get("custom_black_list"))

    def _forward_loss(self, params, buffers, inputs, labels, training):
        net = self.model.network
        net.load_functional_state(params, buffers)
        tin = [Tensor(v, stop_gradient=True, _internal=True) for v in inputs]
        with self._amp_ctx():
            outs = net(*tin)
            outs_list = _to_list(outs)
            loss = None
            if self.model._loss is not None and labels is not None:
                tlab = [Tensor(v, stop_gradient=True, _internal=True)
                        for v in labels]
                loss = self.model._compute_loss(outs_list, tlab)
        new_bufs = {n: b._value for n, b in net.named_buffers()}
        raw_outs = [o._value for o in outs_list]
        return loss, raw_outs, new_bufs

    def _sharding_plan(self):
        """When a mesh is active, build GSPMD shardings: batch on dp(+sp),
        params by TP/ZeRO name rules, slots following their params
        (the declarative replacement for fleet meta-optimizer program
        surgery — SURVEY.md §2.2)."""
        from ..distributed import mesh as mesh_mod
        mesh = mesh_mod.get_mesh()
        if mesh is None or int(np.prod(list(mesh.shape.values()))) == 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..distributed.sharding import build_param_shardings
        net = self.model.network
        opt = self.model._optimizer
        zero = bool(getattr(opt, "_zero_dp", False)) \
            or bool(getattr(net, "_zero_dp", False))
        params, buffers = net.functional_state()
        param_sh = build_param_shardings(params, mesh, zero_dp=zero)
        repl = NamedSharding(mesh, P())
        batch = NamedSharding(mesh, P("dp") if "dp" in mesh.axis_names
                              else P())
        return {"mesh": mesh, "param": param_sh, "repl": repl,
                "batch": batch}

    def _make_train_step(self):
        """The pure fwd+bwd+update step, shared by the jit/GSPMD path
        (_build_train_fn) and the LocalSGD shard_map path."""
        model = self.model
        opt = model._optimizer
        net = model.network
        params, _ = net.functional_state()
        named = {n: p for n, p in net.named_parameters()}
        trainable = {n for n, p in named.items() if not p.stop_gradient}
        meta = opt._param_meta(named)
        amp_cfg = model._amp_configs
        scaler = amp_cfg.get("scaler") if amp_cfg else None

        def step(params, buffers, slots, lr, t, key, inputs, labels,
                 scale_state):
            with _rng.rng_state(key), _tape.no_grad():
                train_p = {k: v for k, v in params.items() if k in trainable}
                frozen_p = {k: v for k, v in params.items()
                            if k not in trainable}

                def loss_of(tp):
                    full = dict(frozen_p)
                    full.update(tp)
                    loss, raw_outs, new_bufs = self._forward_loss(
                        full, buffers, inputs, labels, True)
                    lv = loss._value
                    if scaler is not None:
                        # loss scaling inside the differentiated region
                        # (reference amp/grad_scaler.py scale())
                        lv = lv * scale_state["scale"].astype(lv.dtype)
                    return lv, (raw_outs, new_bufs, loss._value)

                (_, (outs, new_bufs, lval)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_p)
                if scaler is not None:
                    # check_finite_and_unscale + update_loss_scaling fused
                    # into the step; non-finite steps keep old params/slots
                    grads, found, scale_state = scaler.apply_pure(
                        grads, scale_state)
                    new_train, new_slots = opt.apply_gradients_pure(
                        train_p, grads, slots, lr, t, param_meta=meta)
                    keep = lambda old, new: jnp.where(found, old, new)  # noqa: E731
                    new_train = jax.tree_util.tree_map(keep, train_p,
                                                       new_train)
                    new_slots = jax.tree_util.tree_map(keep, dict(slots),
                                                       new_slots)
                else:
                    new_train, new_slots = opt.apply_gradients_pure(
                        train_p, grads, slots, lr, t, param_meta=meta)
                new_params = dict(frozen_p)
                new_params.update(new_train)
            return lval, outs, new_bufs, new_params, new_slots, scale_state

        return step

    def _build_train_fn(self, example_in=(), example_lab=()):
        """(jitted step, mesh layout of (params, buffers, slots) or None
        when no mesh is active)."""
        step = self._make_train_step()
        amp_cfg = self.model._amp_configs
        scaler = amp_cfg.get("scaler") if amp_cfg else None
        plan = self._sharding_plan()
        if plan is None:
            return jax.jit(step, donate_argnums=(0, 1, 2)), None
        # distributed: partition the whole step via GSPMD
        opt_state = self.model._optimizer._slots
        slot_sh = {k: {s: plan["param"][k] for s in opt_state.get(k, {})}
                   for k in opt_state}
        buffers_sh = {n: plan["repl"] for n, _ in
                      self.model.network.named_buffers()}
        scale_sh = jax.tree_util.tree_map(lambda _: plan["repl"],
                                          {"scale": 0, "good": 0, "bad": 0}) \
            if scaler is not None else None

        def data_sh(example):  # scalar leaves (rank 0) cannot ride P('dp')
            def leaf_sh(a):
                if np.ndim(a) < 1:
                    return plan["repl"]
                dp = plan["mesh"].shape.get("dp", 1)
                if dp > 1 and np.shape(a)[0] % dp:
                    # a batch the dp axis cannot divide (e.g. a leaked
                    # wider-than-batch default mesh) degrades to
                    # replicated input, same contract as
                    # sharding._validate_divisible — loudly, not a
                    # pjit divisibility crash
                    from ..core import monitor as _monitor
                    _monitor.stat_add("sharding.nondivisible_fallback")
                    return plan["repl"]
                return plan["batch"]
            return jax.tree_util.tree_map(leaf_sh, tuple(example))

        # new params/slots come back in the layout they go in with: left
        # to the partitioner, a tp mesh may return e.g. the vocab bias
        # P('tp'), which the next step's in_shardings then refuse
        return jax.jit(
            step,
            in_shardings=(plan["param"], buffers_sh, slot_sh, plan["repl"],
                          plan["repl"], plan["repl"], data_sh(example_in),
                          data_sh(example_lab), scale_sh),
            out_shardings=(None, None, None, plan["param"], slot_sh, None),
            donate_argnums=(0, 1, 2)), (plan["param"], buffers_sh, slot_sh)

    # ---- LocalSGD (strategy.localsgd / adaptive_localsgd) ------------------
    def _localsgd_cfg(self):
        """Live strategy.localsgd knob (reference
        meta_optimizers/localsgd_optimizer.py LocalSGDOptimizer /
        AdaptiveLocalSGDOptimizer): requires a mesh with dp>=2. Returns
        None when the plain path applies."""
        strat = getattr(self.model._optimizer, "_dist_strategy", None)
        if strat is None or not (getattr(strat, "localsgd", False)
                                 or getattr(strat, "adaptive_localsgd",
                                            False)):
            return None
        from ..distributed import mesh as mesh_mod
        mesh = mesh_mod.get_mesh()
        if mesh is None or "dp" not in mesh.axis_names \
                or mesh.shape["dp"] < 2:
            return None
        if self.model._amp_configs and \
                self.model._amp_configs.get("scaler"):
            raise ValueError(
                "strategy.localsgd does not compose with dynamic loss "
                "scaling (the reference's LocalSGDOptimizer is likewise "
                "incompatible with AMP program rewriting); use bf16 O2")
        cfg = dict(getattr(strat, "localsgd_configs", {}) or {})
        return {"mesh": mesh, "k": max(1, int(cfg.get("k_steps", 4) or 4)),
                "adaptive": bool(getattr(strat, "adaptive_localsgd", False)),
                "max_k": int(cfg.get("max_k_steps", 16) or 16),
                "rel_tol": float(cfg.get("rel_tol", 0.01) or 0.01)}

    def _build_localsgd_fn(self, k, mesh):
        """shard_map step over dp: each dp shard owns a PRIVATE copy of
        params/slots (leading replica dim), steps locally, and parameters
        are pmean-averaged only every k-th step — one lax.cond'ed ICI
        collective instead of a per-step gradient all-reduce
        (distributed/localsgd.py carries the standalone form)."""
        from jax.sharding import PartitionSpec as P
        step = self._make_train_step()

        def spmd(params, buffers, slots, lr, t, key, inputs, labels,
                 counter):
            one = lambda q: jax.tree_util.tree_map(lambda x: x[0], q)  # noqa: E731
            lift = lambda q: jax.tree_util.tree_map(lambda x: x[None], q)  # noqa: E731
            key = jax.random.fold_in(key, jax.lax.axis_index("dp"))
            lval, outs, new_bufs, new_p, new_s, _ = step(
                one(params), buffers, one(slots), lr, t, key,
                inputs, labels, {})
            c = counter[0] + 1

            def sync(q):
                return jax.tree_util.tree_map(
                    lambda x: jax.lax.pmean(x, "dp"), q)

            new_p = jax.lax.cond(c % k == 0, sync, lambda q: q, new_p)
            # buffers (e.g. BN running stats) stay replicated: average
            new_bufs = sync(new_bufs)
            lval = jax.lax.pmean(lval, "dp")
            return lval, outs, new_bufs, lift(new_p), lift(new_s), c[None]

        st = self._localsgd
        pspec = jax.tree_util.tree_map(lambda _: P("dp"), st["params"])
        sspec = jax.tree_util.tree_map(lambda _: P("dp"), st["slots"])
        bspec = jax.tree_util.tree_map(
            lambda _: P(), {n: 0 for n, _ in
                            self.model.network.named_buffers()})
        from ..distributed import mesh as _mesh_mod
        return jax.jit(_mesh_mod.shard_map(
            spmd, mesh=mesh,
            in_specs=(pspec, bspec, sspec, P(), P(), P(), P("dp"),
                      P("dp"), P("dp")),
            out_specs=(P(), P("dp"), bspec, pspec, sspec, P("dp"))))

    def _train_batch_localsgd(self, cfg, raw_in, raw_lab):
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        model = self.model
        net = model.network
        opt = model._optimizer
        mesh = cfg["mesh"]
        if self._localsgd is None:
            params, buffers = net.functional_state()
            named = dict(net.named_parameters())
            opt._ensure_slots({n: v for n, v in params.items()
                               if not named[n].stop_gradient})
            slots = {n: opt._slots[n] for n in opt._slots
                     if n in params and not named[n].stop_gradient}
            n = mesh.shape["dp"]
            sh = NamedSharding(mesh, P("dp"))
            rep = lambda q: jax.tree_util.tree_map(  # noqa: E731
                lambda x: jax.device_put(
                    jnp.broadcast_to(x[None], (n,) + x.shape), sh), q)
            self._localsgd = {
                "params": rep(params), "slots": rep(slots),
                "counter": jax.device_put(jnp.zeros((n,), jnp.int32), sh),
                "k": cfg["k"], "fns": {}, "last_sync_loss": None}
        st = self._localsgd
        k = st["k"]
        if k not in st["fns"]:
            st["fns"][k] = self._build_localsgd_fn(k, mesh)
        opt._step_count += 1
        params, buffers = net.functional_state()
        lval, outs, new_bufs, st["params"], st["slots"], st["counter"] = \
            st["fns"][k](st["params"], buffers, st["slots"],
                         jnp.asarray(opt.get_lr(), jnp.float32),
                         jnp.asarray(opt._step_count, jnp.int32),
                         _rng.next_key(), raw_in, raw_lab, st["counter"])
        self._write_back({}, new_bufs)
        c = int(np.asarray(st["counter"])[0])
        if cfg["adaptive"] and c % k == 0:
            loss = float(np.asarray(lval))
            last = st["last_sync_loss"]
            if last is not None and loss > last * (1 - cfg["rel_tol"]):
                st["k"] = min(k + 1, cfg["max_k"])
            st["last_sync_loss"] = loss
        if c % k == 0:
            # synced boundary: the replicas agree — surface the averaged
            # params to the net so eval/save/callbacks see fresh weights
            self._write_back(jax.tree_util.tree_map(
                lambda x: x[0], st["params"]), {})
        return lval, outs

    def finalize_localsgd(self):
        """Final cross-replica average written back into the network;
        called at fit() end and before eval/predict/save."""
        st = self._localsgd
        if st is None:
            return
        avg = jax.tree_util.tree_map(
            lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(
                x.dtype), st["params"])
        self._write_back(avg, {})
        slot_avg = jax.tree_util.tree_map(
            lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(
                x.dtype), st["slots"])
        self.model._optimizer._slots.update(slot_avg)
        self._localsgd = None

    def _build_grad_fn(self):
        """Forward+backward only — used for gradient accumulation
        (GradientMergeOptimizer analog, reference fluid/optimizer.py:5004).
        With a GradScaler the micro-batch loss is scaled, so accumulated
        grads stay scaled until the apply step unscales them once."""
        net = self.model.network
        named = {n: p for n, p in net.named_parameters()}
        trainable = {n for n, p in named.items() if not p.stop_gradient}
        amp_cfg = self.model._amp_configs
        scaler = amp_cfg.get("scaler") if amp_cfg else None

        def gstep(params, buffers, key, inputs, labels, scale):
            with _rng.rng_state(key), _tape.no_grad():
                train_p = {k: v for k, v in params.items() if k in trainable}
                frozen_p = {k: v for k, v in params.items()
                            if k not in trainable}

                def loss_of(tp):
                    full = dict(frozen_p)
                    full.update(tp)
                    loss, raw_outs, new_bufs = self._forward_loss(
                        full, buffers, inputs, labels, True)
                    lv = loss._value
                    if scaler is not None:
                        lv = lv * scale.astype(lv.dtype)
                    return lv, (raw_outs, new_bufs, loss._value)

                (_, (outs, new_bufs, lval)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_p)
            return lval, outs, new_bufs, grads

        return jax.jit(gstep)

    def _build_apply_fn(self):
        opt = self.model._optimizer
        named = dict(self.model.network.named_parameters())
        meta = opt._param_meta(named)
        amp_cfg = self.model._amp_configs
        scaler = amp_cfg.get("scaler") if amp_cfg else None

        def apply(params, slots, grads, lr, t, inv_count, scale_state):
            if scaler is not None:
                # one unscale+finite-check over the merged grads, then the
                # same found_inf gating as the fused path
                grads, found, scale_state = scaler.apply_pure(
                    grads, scale_state)
            grads = {k: g * inv_count for k, g in grads.items()}
            train_p = {k: params[k] for k in grads}
            new_train, new_slots = opt.apply_gradients_pure(
                train_p, grads, slots, lr, t, param_meta=meta)
            if scaler is not None:
                keep = lambda old, new: jnp.where(found, old, new)  # noqa: E731
                new_train = jax.tree_util.tree_map(keep, train_p, new_train)
                new_slots = jax.tree_util.tree_map(keep, dict(slots),
                                                   new_slots)
            new_params = dict(params)
            new_params.update(new_train)
            return new_params, new_slots, scale_state

        return jax.jit(apply, donate_argnums=(0, 1))

    def _build_eval_fn(self):
        def step(params, buffers, key, inputs, labels):
            with _rng.rng_state(key), _tape.no_grad():
                loss, raw_outs, _ = self._forward_loss(
                    params, buffers, inputs, labels, False)
            lval = loss._value if loss is not None else jnp.zeros(())
            return lval, raw_outs

        return jax.jit(step)

    def _build_pred_fn(self):
        def step(params, buffers, key, inputs):
            with _rng.rng_state(key), _tape.no_grad():
                _, raw_outs, _ = self._forward_loss(params, buffers, inputs,
                                                    None, False)
            return raw_outs

        return jax.jit(step)

    # ---- public steps ------------------------------------------------------
    def train_batch(self, inputs, labels, update=True):
        with _eager_scope():
            return self._train_batch_impl(inputs, labels, update=update)

    def _train_batch_impl(self, inputs, labels, update=True):
        model = self.model
        net = model.network
        net.train()
        opt = model._optimizer
        params, buffers = net.functional_state()
        named = dict(net.named_parameters())
        opt._ensure_slots({k: v for k, v in params.items()
                           if not named[k].stop_gradient})
        slots = {k: opt._slots[k] for k in opt._slots
                 if k in params and not named[k].stop_gradient}
        raw_in = tuple(_to_raw(v) for v in inputs)
        raw_lab = tuple(_to_raw(v) for v in labels)
        accumulating = (not update) or self._accum_grads is not None

        lcfg = self._localsgd_cfg()
        if lcfg is not None and not accumulating:
            return self._train_batch_localsgd(lcfg, raw_in, raw_lab)

        if not accumulating:
            # fast path: forward+backward+update fused in one XLA program
            if self._train_fn is None:
                from ..core import trace as _trace
                # a rebuild (and with it a recompile) shows as this span
                with _trace.span("hapi/build_train_fn"):
                    self._train_fn, state_sh = self._build_train_fn(
                        raw_in, raw_lab)
                if state_sh is not None:
                    # seat the host-built state in its mesh layout once:
                    # step 1 then has step 2's signature, and the step
                    # compiles once instead of twice
                    params, buffers, slots = jax.device_put(
                        (params, buffers, slots), state_sh)
            amp_cfg = self.model._amp_configs
            scaler = amp_cfg.get("scaler") if amp_cfg else None
            scale_state = scaler.scale_state() if scaler is not None else {}
            opt._step_count += 1
            lval, outs, new_bufs, new_params, new_slots, scale_state = \
                self._train_fn(
                    params, buffers, slots,
                    jnp.asarray(opt.get_lr(), jnp.float32),
                    jnp.asarray(opt._step_count, jnp.int32),
                    _rng.next_key(), raw_in, raw_lab, scale_state)
            if scaler is not None:
                scaler.load_scale_state(scale_state)
            from ..core import flags as _flags
            if _flags.flag("FLAGS_check_nan_inf"):
                from ..core.numeric_check import sweep
                sweep({"loss": lval, "params": new_params},
                      "train_batch step")
            self._write_back(new_params, new_bufs)
            opt._slots.update(new_slots)
            return lval, outs

        # accumulation path: grads summed across micro-batches, applied on
        # the update call (grads averaged by micro-batch count)
        amp_cfg = self.model._amp_configs
        scaler = amp_cfg.get("scaler") if amp_cfg else None
        if self._grad_fn is None:
            self._grad_fn = self._build_grad_fn()
        scale = scaler.scale_state()["scale"] if scaler is not None \
            else jnp.ones((), jnp.float32)
        lval, outs, new_bufs, grads = self._grad_fn(
            params, buffers, _rng.next_key(), raw_in, raw_lab, scale)
        self._write_back({}, new_bufs)
        self._restore(params, {})
        if self._accum_grads is None:
            self._accum_grads = grads
            self._accum_count = 1
        else:
            self._accum_grads = jax.tree_util.tree_map(
                jnp.add, self._accum_grads, grads)
            self._accum_count += 1
        if update:
            if self._apply_fn is None:
                self._apply_fn = self._build_apply_fn()
            opt._step_count += 1
            scale_state = scaler.scale_state() if scaler is not None else {}
            new_params, new_slots, scale_state = self._apply_fn(
                params, slots, self._accum_grads,
                jnp.asarray(opt.get_lr(), jnp.float32),
                jnp.asarray(opt._step_count, jnp.int32),
                jnp.asarray(1.0 / self._accum_count, jnp.float32),
                scale_state)
            if scaler is not None:
                scaler.load_scale_state(scale_state)
            self._write_back(new_params, {})
            opt._slots.update(new_slots)
            self._accum_grads = None
            self._accum_count = 0
        return lval, outs

    def eval_batch(self, inputs, labels):
        with _eager_scope():
            return self._eval_batch_impl(inputs, labels)

    def _eval_batch_impl(self, inputs, labels):
        self.finalize_localsgd()
        net = self.model.network
        net.eval()
        params, buffers = net.functional_state()
        if self._eval_fn is None:
            self._eval_fn = self._build_eval_fn()
        lval, outs = self._eval_fn(
            params, buffers, _rng.next_key(),
            tuple(_to_raw(v) for v in inputs),
            tuple(_to_raw(v) for v in labels) if labels else None)
        self._restore(params, buffers)
        return lval, outs

    def predict_batch(self, inputs):
        with _eager_scope():
            return self._predict_batch_impl(inputs)

    def _predict_batch_impl(self, inputs):
        self.finalize_localsgd()
        net = self.model.network
        net.eval()
        params, buffers = net.functional_state()
        if self._pred_fn is None:
            self._pred_fn = self._build_pred_fn()
        outs = self._pred_fn(params, buffers, _rng.next_key(),
                             tuple(_to_raw(v) for v in inputs))
        self._restore(params, buffers)
        return outs

    def _write_back(self, new_params, new_bufs):
        net = self.model.network
        for n, p in net.named_parameters():
            if n in new_params:
                p._value = new_params[n]
                p._node = None
                p.grad = None
        for n, b in net.named_buffers():
            if n in new_bufs:
                b._value = new_bufs[n]
                b._node = None

    def _restore(self, params, buffers):
        # forward inside jit seats tracers into the layer; put values back
        net = self.model.network
        net.load_functional_state(params, buffers)


@contextlib.contextmanager
def _eager_scope():
    """The hapi engine is mode-independent (one compiled step replaces
    BOTH reference adapters, StaticGraphAdapter :223 / DynamicGraphAdapter
    :608) — it always traces its own jitted program. Suspend static-graph
    recording for the duration so `paddle.enable_static()` elsewhere in
    the script doesn't make engine ops append to a Program."""
    from ..static.program import _state
    was = _state.enabled
    _state.enabled = False
    try:
        yield
    finally:
        _state.enabled = was


class Model:
    def __init__(self, network, inputs=None, labels=None):
        from ..static.program import Variable as _StaticVar
        for _n, p in network.named_parameters():
            if isinstance(p, _StaticVar) and p._value is None:
                raise TypeError(
                    "Model received a network built under "
                    "paddle.enable_static() (its parameters are static "
                    "Variables). The hapi engine compiles its own step and "
                    "serves both execution modes — construct the network "
                    "in dygraph (before enable_static), or use the "
                    "paddle.static Executor workflow for Program-based "
                    "training.")
        self.network = network
        self._inputs = _to_list(inputs)
        self._labels = _to_list(labels)
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._engine = _CompiledEngine(self)
        self.stop_training = False

    # -- setup ---------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        if loss is not None and not (isinstance(loss, Layer) or callable(loss)):
            raise TypeError("loss must be a Layer or callable")
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metrics must be Metric instances, got {m}")
        self._amp_configs = self._parse_amp(amp_configs)
        self._apply_strategy_recompute()
        return self

    def _apply_strategy_recompute(self):
        """strategy.recompute -> Layer.enable_recompute on the designated
        blocks (reference RecomputeOptimizer applied via fleet strategy;
        fluid/optimizer.py:4526). recompute_configs:
          - "layers": fnmatch patterns over named_sublayers, or
          - default: every TransformerEncoderLayer/TransformerDecoderLayer.
        """
        strat = getattr(self._optimizer, "_dist_strategy", None)
        if strat is None or not getattr(strat, "recompute", False):
            return
        cfg = getattr(strat, "recompute_configs", {}) or {}
        policy = cfg.get("policy", "nothing")
        patterns = cfg.get("layers")
        net = self.network
        if patterns:
            import fnmatch
            hits = [sub for name, sub in net.named_sublayers()
                    if any(fnmatch.fnmatch(name, p) for p in patterns)]
        else:
            from ..nn.layer.transformer import (TransformerDecoderLayer,
                                                TransformerEncoderLayer)
            hits = [sub for _, sub in net.named_sublayers()
                    if isinstance(sub, (TransformerEncoderLayer,
                                        TransformerDecoderLayer))]
        for sub in hits:
            sub.enable_recompute(policy=policy)

    def _parse_amp(self, amp_configs):
        """amp_configs: None | 'O1'/'O2' | dict (reference hapi/model.py
        _check_amp_configs + amp/auto_cast.py). O2 casts parameters to the
        amp dtype and enables f32 master weights in the optimizer."""
        if amp_configs is None and self._optimizer is not None:
            # fleet strategy amp knob reaches the engine declaratively
            strat = getattr(self._optimizer, "_dist_strategy", None)
            if strat is not None and getattr(strat, "amp", False):
                amp_configs = dict(strat.amp_configs)
                if amp_configs.pop("use_pure_bf16", False):
                    amp_configs.setdefault("level", "O2")
        if amp_configs is None:
            return None
        from .. import amp as amp_mod
        if isinstance(amp_configs, str):
            amp_configs = {"level": amp_configs}
        cfg = dict(amp_configs)
        level = cfg.get("level", "O1")
        if level == "O0":
            return None
        if level not in ("O1", "O2"):
            raise ValueError(f"amp level must be O0/O1/O2, got {level!r}")
        dtype = cfg.get("dtype", "bfloat16")
        scaler = None
        # loss scaling matters for f16's narrow exponent range; bf16 matches
        # f32's range so the scaler is skipped unless explicitly forced
        want_scaler = (str(dtype) in ("float16", "fp16")
                       and (cfg.get("use_dynamic_loss_scaling", True)
                            or "init_loss_scaling" in cfg)) \
            or cfg.get("force_loss_scaling", False)
        if want_scaler:
            scaler = amp_mod.GradScaler(
                init_loss_scaling=cfg.get("init_loss_scaling", 2.0 ** 15),
                incr_ratio=cfg.get("incr_ratio", 2.0),
                decr_ratio=cfg.get("decr_ratio", 0.5),
                incr_every_n_steps=cfg.get("incr_every_n_steps", 1000),
                decr_every_n_nan_or_inf=cfg.get("decr_every_n_nan_or_inf", 2),
                use_dynamic_loss_scaling=cfg.get(
                    "use_dynamic_loss_scaling", True))
        if level == "O2" and self._optimizer is not None:
            amp_mod.decorate(self.network, self._optimizer, level="O2",
                             dtype=dtype)
        return {"level": level, "dtype": dtype, "scaler": scaler,
                "custom_white_list": cfg.get("custom_white_list"),
                "custom_black_list": cfg.get("custom_black_list")}

    def _compute_loss(self, outputs, labels):
        loss = self._loss
        if isinstance(loss, list):
            vals = [fn(o, l) for fn, o, l in zip(loss, outputs, labels)]
            total = vals[0]
            for v in vals[1:]:
                total = total + v
            return total
        return loss(*(outputs + labels))

    # -- batch-level API -----------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        lval, outs = self._engine.train_batch(_to_list(inputs),
                                              _to_list(labels),
                                              update=update)
        return self._wrap_loss(lval)

    def eval_batch(self, inputs, labels=None):
        lval, outs = self._engine.eval_batch(_to_list(inputs),
                                             _to_list(labels))
        return self._wrap_loss(lval)

    def predict_batch(self, inputs):
        outs = self._engine.predict_batch(_to_list(inputs))
        return [np.asarray(o) for o in outs]

    @staticmethod
    def _wrap_loss(lval):
        return [float(np.asarray(lval))]

    # -- loops ---------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None,
            auto_checkpoint_dir=None, auto_checkpoint_freq=50,
            keep_checkpoint_max=3):
        """... `auto_checkpoint_dir` enables preemption-safe training:
        async step-atomic checkpoints (params, optimizer, scaler, rng,
        counters) every `auto_checkpoint_freq` steps, keep-latest-
        `keep_checkpoint_max`, and resume-from-latest on the next fit()
        (reference fluid/incubate/checkpoint/auto_checkpoint.py:71)."""
        from ..io import DataLoader, Dataset

        assert self._optimizer is not None and self._loss is not None, \
            "call prepare(optimizer=..., loss=...) before fit()"
        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        if eval_data is not None and isinstance(eval_data, Dataset):
            eval_loader = DataLoader(eval_data, batch_size=batch_size,
                                     num_workers=num_workers)
        else:
            eval_loader = eval_data

        do_eval = eval_loader is not None
        try:
            steps = len(train_loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                steps=steps, log_freq=log_freq,
                                save_freq=save_freq, save_dir=save_dir,
                                verbose=verbose,
                                metrics=self._metrics_name())
        def _loader_state():
            if hasattr(train_loader, "state_dict"):
                try:
                    return train_loader.state_dict()
                except Exception:
                    return None
            return None

        acp, start_epoch, skip_steps, step_offset = None, 0, 0, 0
        if auto_checkpoint_dir is not None:
            from ..incubate.checkpoint import TrainingCheckpoint
            acp = TrainingCheckpoint(auto_checkpoint_dir,
                                     keep=keep_checkpoint_max,
                                     save_interval_steps=auto_checkpoint_freq)
            resumable = train_loader if hasattr(
                train_loader, "load_state_dict") else None
            counters = acp.restore_into(self, data_loader=resumable)
            if counters is not None:
                self._global_step = counters["global_step"]
                start_epoch = counters["epoch"]
                skip_steps = counters["step"] + 1
                if counters.get("data_resumed"):
                    # the loader fast-forwards itself (sampler-level
                    # skip, exact shuffle state) — fit only offsets the
                    # step numbering instead of replaying batches
                    step_offset, skip_steps = skip_steps, 0
                    # a cursor at the epoch boundary — the natural end
                    # OR fit's steps= cap (a boundary the loader can't
                    # see) — means that epoch is DONE: roll fit's epoch
                    # label in step with the loader's auto-roll, else
                    # the resumed loop trains one extra loader epoch
                    # under a stale label
                    bounds = [steps]
                    try:
                        bounds.append(len(train_loader))
                    except TypeError:
                        pass
                    epoch_len = min(b for b in bounds if b is not None) \
                        if any(b is not None for b in bounds) else None
                    if epoch_len is not None and step_offset >= epoch_len:
                        start_epoch, step_offset = start_epoch + 1, 0
                        # steps= truncation: advance the loader past the
                        # truncated epoch's permutation so the next
                        # iteration starts the new epoch fresh instead
                        # of replaying the truncated epoch's tail (a
                        # natural epoch end auto-rolls; this is a no-op
                        # there)
                        if hasattr(resumable, "roll_resumed_epoch"):
                            resumable.roll_resumed_epoch()
                elif steps is not None and skip_steps >= steps:
                    start_epoch, skip_steps = start_epoch + 1, 0
            else:
                self._global_step = 0
        self._acp = acp

        guard = contextlib.nullcontext()
        if acp is not None:
            from ..incubate.checkpoint import PreemptionGuard
            self._acp_pos = (start_epoch,
                             max(skip_steps + step_offset - 1, 0))
            # the guard capture uses the data state snapshotted at the
            # last COMPLETED batch (kept in step with _acp_pos by
            # _run_one_epoch), never the live loader cursor: a SIGTERM
            # mid-batch would otherwise save a cursor one batch ahead
            # of the applied optimizer state and the resume would skip
            # that batch
            self._acp_data_state = _loader_state()
            guard = PreemptionGuard(
                acp, lambda: (self._global_step,
                              acp.capture(self, *self._acp_pos,
                                          self._global_step,
                                          data_state=getattr(
                                              self, "_acp_data_state",
                                              None))))

        cbks.on_begin("train")
        logs = {}
        with guard:
            for epoch in range(start_epoch, epochs):
                cbks.on_epoch_begin(epoch)
                logs = self._run_one_epoch(train_loader, cbks, "train",
                                           num_iters=num_iters,
                                           accum=accumulate_grad_batches,
                                           epoch=epoch,
                                           skip_steps=skip_steps,
                                           step_offset=step_offset,
                                           log_freq=log_freq)
                skip_steps = 0
                step_offset = 0
                cbks.on_epoch_end(epoch, logs)
                if do_eval and epoch % eval_freq == 0:
                    eval_logs = self.evaluate(eval_loader, callbacks=cbks,
                                              _inside_fit=True)
                    logs.update({f"eval_{k}": v
                                 for k, v in eval_logs.items()})
                if self.stop_training:
                    break
        if acp is not None:
            acp.wait()
        self._engine.finalize_localsgd()
        cbks.on_end("train", logs)
        return self

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, _inside_fit=False):
        from ..io import DataLoader, Dataset
        if isinstance(eval_data, Dataset):
            loader = DataLoader(eval_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = eval_data
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            inputs, labels = self._split_batch(batch)
            lval, outs = self._engine.eval_batch(inputs, labels)
            losses.append(float(np.asarray(lval)))
            self._update_metrics(outs, labels)
        logs = {"loss": float(np.mean(losses)) if losses else 0.0}
        for m in self._metrics:
            res = m.accumulate()
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = res if isinstance(res, list) else [res]
            logs.update(dict(zip(names, vals)))
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        from ..io import DataLoader, Dataset
        if isinstance(test_data, Dataset):
            loader = DataLoader(test_data, batch_size=batch_size,
                                num_workers=num_workers)
        else:
            loader = test_data
        outputs = []
        for batch in loader:
            inputs, _ = self._split_batch(batch, allow_no_label=True)
            outs = self.predict_batch(inputs)
            outputs.append(outs)
        if not outputs:
            return []
        # transpose: list of per-batch lists -> per-output lists
        n_out = len(outputs[0])
        merged = [[b[i] for b in outputs] for i in range(n_out)]
        if stack_outputs:
            merged = [np.concatenate(m) for m in merged]
        return merged

    def _run_one_epoch(self, loader, cbks, mode, num_iters=None, accum=1,
                       epoch=0, skip_steps=0, step_offset=0, log_freq=10):
        import itertools
        from collections import deque
        from ..core import flags as _flags
        from ..core import trace as _trace
        for m in self._metrics:
            m.reset()
        logs = {}
        acp = getattr(self, "_acp", None)
        # async hot loop (docs/async_executor.md): the per-step
        # `float(np.asarray(loss))` host sync was the only thing forcing
        # the loop to wait for the device. With no metrics (metric.update
        # reads the outputs on host every batch) and no grad accumulation
        # bookkeeping, logs["loss"] becomes a _LazyLoss and the window
        # keeps up to FLAGS_executor_max_inflight steps un-materialized;
        # it drains at log_freq boundaries, at the window bound, and
        # whenever a consumer actually reads a loss. An in-flight failure
        # surfaces at the next drain, naming the step.
        inflight = int(_flags.flag("FLAGS_executor_max_inflight"))
        async_loop = (mode == "train" and inflight > 0
                      and not self._metrics and accum <= 1)
        window: deque = deque()

        def drain(through=None):
            # through=None retires only past the window bound; a boundary
            # passes `through` to materialize everything up to that step
            while window and ((through is not None
                               and window[0].step <= through)
                              or len(window) > inflight):
                # host blocked on the device
                with _trace.span("fit/drain", step=window[0].step):
                    window.popleft()._materialize()

        from ..distributed import elastic as _elastic
        # One `fit/step` span per iteration; its children are the step's
        # host phases: fit/next_batch (the loader, pulled by hand so the
        # pull is inside the span), fit/callbacks (begin, then end),
        # fit/dispatch (the engine's train_batch) and fit/drain (above).
        batches = iter(loader)
        for step in itertools.count(step_offset):
            step_span = _trace.begin("fit/step", step=step)
            try:
                with _trace.span("fit/next_batch"):
                    batch = next(batches, _NO_BATCH)
                if batch is _NO_BATCH or step < skip_steps:
                    _trace.end(step_span, discard=True)  # not a step
                    if batch is _NO_BATCH:
                        break
                    continue  # resumed mid-epoch: fast-forward
                with _trace.span("fit/callbacks"):
                    cbks.on_batch_begin(mode, step, logs)
                inputs, labels = self._split_batch(batch)
                update = accum <= 1 or (step + 1) % accum == 0
                with _trace.span("fit/dispatch"):
                    lval, outs = self._engine.train_batch(inputs, labels,
                                                          update=update)
                if self._lr_sched_step_on_batch():
                    self._optimizer._learning_rate.step()
                if async_loop:
                    lazy = _LazyLoss(step, lval, drain)
                    window.append(lazy)
                    if (step + 1) % max(log_freq, 1) == 0:
                        drain(through=step)  # boundary: window retired
                    else:
                        drain()  # retire past the window bound only
                    logs["loss"] = lazy  # exact for whoever reads it
                else:
                    with _trace.span("fit/drain", step=step):
                        logs["loss"] = float(np.asarray(lval))
                logs["batch_size"] = np.asarray(inputs[0]).shape[0]
                metric_logs = self._update_metrics(outs, labels)
                logs.update(metric_logs)
                if mode == "train":
                    _elastic.notify_step()  # StallMonitor/Heartbeat pulse
                if acp is not None and mode == "train":
                    # account the completed batch BEFORE callbacks: a
                    # SIGTERM raised from a callback must capture this
                    # step as done
                    self._global_step = getattr(self, "_global_step", 0) + 1
                    self._acp_pos = (epoch, step)
                    data_state = None
                    if hasattr(loader, "state_dict"):
                        try:
                            data_state = loader.state_dict()
                        except Exception:
                            data_state = None
                    # batch-end snapshot for the PreemptionGuard capture:
                    # consistent with _acp_pos/_global_step by construction
                    self._acp_data_state = data_state
                    acp.maybe_save(self, epoch, step, self._global_step,
                                   data_state=data_state)
                with _trace.span("fit/callbacks"):
                    cbks.on_batch_end(mode, step, logs)
                if num_iters is not None and step + 1 >= num_iters:
                    break
            except BaseException as e:
                step_span.attrs.setdefault("error", type(e).__name__)
                raise
            finally:
                _trace.end(step_span)
        if window:  # epoch boundary: materialize the tail
            drain(through=window[-1].step)
        if async_loop and isinstance(logs.get("loss"), _LazyLoss):
            logs["loss"] = logs["loss"].value()  # plain float leaves fit
        if self._lr_sched_step_on_epoch():
            self._optimizer._learning_rate.step()
        return logs

    def _lr_sched_step_on_batch(self):
        from ..optimizer import lr as lr_mod
        sched = self._optimizer._lr_scheduler if self._optimizer else None
        return isinstance(sched, (lr_mod.NoamDecay, lr_mod.OneCycleLR,
                                  lr_mod.CyclicLR, lr_mod.LinearWarmup))

    def _lr_sched_step_on_epoch(self):
        sched = self._optimizer._lr_scheduler if self._optimizer else None
        return sched is not None and not self._lr_sched_step_on_batch()

    def _update_metrics(self, outs, labels):
        logs = {}
        for m in self._metrics:
            pre = m.compute(outs[0], *[np.asarray(_to_raw(l)) for l in labels])
            if isinstance(pre, tuple):
                m.update(*pre)
            else:
                m.update(pre)
            res = m.accumulate()
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = res if isinstance(res, list) else [res]
            logs.update(dict(zip(names, vals)))
        return logs

    def _split_batch(self, batch, allow_no_label=False):
        n_in = max(len(self._inputs), 1)
        if isinstance(batch, (list, tuple)):
            batch = list(batch)
            if len(batch) == 1:
                return batch, []
            if allow_no_label and len(batch) <= n_in:
                return batch, []
            inputs = batch[:n_in]
            labels = batch[n_in:]
            return inputs, labels
        return [batch], []

    def _metrics_name(self):
        out = ["loss"]
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            out.extend(names)
        return out

    # -- persistence ---------------------------------------------------------
    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def state_dict(self):
        return self.network.state_dict()

    def save(self, path, training=True):
        """path prefix: writes {path}.pdparams (+ {path}.pdopt if training)."""
        self._engine.finalize_localsgd()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            _save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        state = _load(path + ".pdparams")
        self.network.set_state_dict(state)
        opt_path = path + ".pdopt"
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(opt_path)):
            self._optimizer.set_state_dict(_load(opt_path))
        # drop compiled steps: weights changed wholesale
        self._engine = _CompiledEngine(self)
        return self

    def summary(self, input_size=None, dtype=None):
        if input_size is not None:
            # full layer table with output shapes (hapi/summary.py — the
            # single implementation behind paddle.summary too)
            from .summary import summary as _summary
            return _summary(self.network, input_size,
                            dtypes=[dtype] if dtype else None)
        rows = []
        total = trainable = 0
        for name, p in self.network.named_parameters():
            rows.append((name, p.shape, p.size))
            total += p.size
            if not p.stop_gradient:
                trainable += p.size
        width = max((len(r[0]) for r in rows), default=10) + 2
        lines = [f"{'Layer (param)':<{width}}{'Shape':<20}{'Params':<12}"]
        for name, shape, size in rows:
            lines.append(f"{name:<{width}}{str(list(shape)):<20}{size:<12}")
        lines.append(f"Total params: {total:,}")
        lines.append(f"Trainable params: {trainable:,}")
        text = "\n".join(lines)
        print(text)
        return {"total_params": total, "trainable_params": trainable}
