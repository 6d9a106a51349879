"""Driver `train_fit`: one training cell through `paddle.Model.fit`.

Set-up: seeded network, `prepare(optimizer, criterion, amp)`, a seeded
dataset behind the normal DataLoader, `warmup_steps` steps (the first one
compiles). The window opens and closes on a drained step: the benchmark's
callback reads `float(logs["loss"])` at those two steps only; every other
step leaves fit's lazy loss alone, so `_run_one_epoch`'s in-flight window
runs as users run it. Epochs of `steps_per_epoch` steps cycle until the
window closes; the callback then leaves `fit` by raising.

Traffic keys: per_chip_batch, seq_len, steps_per_epoch, warmup_steps,
log_freq, dataset (kwargs of the config's dataset class), optional
mesh ({"dp": 4}: `mesh.init_mesh` first, then the same prepare/fit, global
batch = per_chip_batch x chips), trace_seconds (with --trace 1 the
profiler covers that many seconds of the same loop right after the window).
"""
from __future__ import annotations

import math
import time

from benchmark.lib import accounting, profiler
from benchmark.lib.build import build_net, load_object, model_kwargs, resolve


class WindowClosed(Exception):
    """Raised by the callback to leave `fit` when the window has closed."""


def make_callback(seconds, warmup_steps, trace_dir=None, trace_seconds=3.0):
    from paddle_tpu.hapi.callbacks import Callback

    class Window(Callback):
        def __init__(self):
            super().__init__()
            self.losses = []          # fit's lazy losses, one per step
            self.t_open = self.t_close = None
            self.step_open = self.step_close = None
            self.compiles_in_window = None
            self._compiles_open = None
            self.loader_gaps = []     # s between batch_end(k), begin(k+1)
            self._t_end = None
            self._trace_t0 = None

        def on_train_batch_begin(self, step, logs=None):
            if (self.t_open is not None and self.t_close is None
                    and self._t_end is not None):
                self.loader_gaps.append(time.perf_counter() - self._t_end)

        def on_train_batch_end(self, step, logs=None):
            lazy = logs["loss"]
            self.losses.append(lazy)
            n = len(self.losses)
            now = time.perf_counter()
            if self.t_open is None:
                if n >= warmup_steps:
                    float(lazy)       # drain: the device has done step n
                    self._compiles_open = accounting.snapshot()[
                        "backend_compiles"]
                    self.step_open = n
                    self.t_open = time.perf_counter()
            elif self.t_close is None:
                if now - self.t_open >= seconds:
                    float(lazy)
                    self.t_close = time.perf_counter()
                    self.step_close = n
                    self.compiles_in_window = accounting.snapshot()[
                        "backend_compiles"] - self._compiles_open
                    if trace_dir is None:
                        raise WindowClosed
                    # the profiler runs on the same steady loop right after
                    # the window, so that its start and stop (seconds of
                    # stalled host) are in no counter the readers use
                    profiler.start(trace_dir)
                    self._trace_t0 = time.perf_counter()
            elif now - self._trace_t0 >= trace_seconds:
                float(lazy)           # the traced steps have run
                profiler.stop()
                raise WindowClosed
            self._t_end = time.perf_counter()

    return Window()


def build_model(config, traffic, seed):
    """The prepared `paddle.Model` of the cell (mesh declared first)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    if traffic.get("mesh"):
        mesh_mod.init_mesh(dict(traffic["mesh"]))
    net = build_net(config, seed)
    train = config["train"]
    model = paddle.Model(net)
    opt = load_object(train["optimizer"]["class"])(
        parameters=net.parameters(), **train["optimizer"]["kwargs"])
    criterion = load_object(train["criterion"]["class"])(
        **resolve(train["criterion"]["kwargs"], config))
    return model.prepare(opt, criterion, amp_configs=train["amp"])


def fit_window(model, config, traffic, seed, seconds, chips, trace_dir=None):
    """Run fit until the window closes; returns the callback."""
    batch = int(traffic["per_chip_batch"]) * chips
    data = load_object(config["train"]["dataset"]["class"])(
        n=int(traffic["steps_per_epoch"]) * batch, seed=seed,
        seq_len=int(traffic["seq_len"]),
        **resolve(config["train"]["dataset"]["kwargs"], config),
        **traffic.get("dataset", {}))
    cb = make_callback(seconds, int(traffic["warmup_steps"]), trace_dir,
                       float(traffic.get("trace_seconds", 3.0)))
    try:
        # shuffle off: the same seed gives the same batches in the same
        # order. num_workers=0: forked loader workers under a live TPU
        # client are untested (docs/chip_runs.md)
        model.fit(data, batch_size=batch, epochs=10 ** 6, shuffle=False,
                  drop_last=True, num_workers=0, verbose=0,
                  log_freq=int(traffic.get("log_freq", 10)), callbacks=[cb])
    except WindowClosed:
        pass
    return cb


def check(model, cb, chips, platform):
    """(why the run is not correct: an empty list when it is; the losses;
    every number compared beside its limit, {name: [value, limit]})."""
    import jax
    why = []
    losses = [float(x) for x in cb.losses]   # all materialised by now
    k = max(1, len(losses) // 10)
    first, last = sum(losses[:k]) / k, sum(losses[-k:]) / k
    nonfinite = sum(not math.isfinite(x) for x in losses)
    if nonfinite:
        why.append("non-finite loss")
    elif not last < first:
        why.append(f"loss did not fall: first tenth {first:.4f}, last "
                   f"{last:.4f}")
    variants = model._engine._train_fn._cache_size()
    if variants != 1:
        why.append(f"train step compiled in {variants} variants")
    if cb.compiles_in_window:
        why.append(f"{cb.compiles_in_window} compiles inside the window")
    values = [p._value for p in model.network.parameters()]
    if any({d.platform for d in v.devices()} != {platform} for v in values):
        why.append(f"a parameter is not on {platform}")
    if chips > 1:
        if min(len(v.sharding.device_set) for v in values) != chips:
            why.append(f"a parameter is not on all {chips} devices")
        held = [(d.memory_stats() or {}).get("bytes_in_use", 1)
                for d in jax.devices()[:chips]]
        if not all(held):
            why.append(f"a device holds no bytes: {held}")
    compared = {"losses_nonfinite": [nonfinite, 0],
                "loss_last_tenth_under_first": [last, first],
                "step_variants": [variants, 1],
                "compiles_in_window": [cb.compiles_in_window, 0]}
    return why, losses, compared


def run(cell):
    import jax
    config, traffic = cell.config, cell.traffic
    mesh = traffic.get("mesh") or {}
    chips = math.prod(mesh.values()) if mesh else 1
    if chips != cell.chips:
        raise SystemExit(f"train_fit: the mix's mesh {mesh} is {chips} "
                         f"chip(s), the cell says {cell.chips}")
    model = build_model(config, traffic, cell.seed)
    cb = fit_window(model, config, traffic, cell.seed, cell.seconds, chips,
                    cell.trace_dir if cell.trace else None)
    why, losses, compared = check(model, cb, chips,
                                  jax.devices()[0].platform)
    steps = cb.step_close - cb.step_open
    window = losses[cb.step_open:cb.step_close]
    failed = sum(not math.isfinite(x) for x in window)
    window_s = cb.t_close - cb.t_open
    print(f"train_fit: {steps} steps in {window_s:.3f} s, loss "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}, {len(losses)} steps in all",
          flush=True)
    return {
        "correct": not why and not failed, "why_incorrect": why,
        "compared": compared, "attempted": steps, "failed": failed,
        "setup_s": cb.t_open - cell.t_process_start,
        "window_s": window_s, "chips": chips, "steps": steps,
        "tokens": steps * int(traffic["per_chip_batch"]) * chips
        * int(traffic["seq_len"]),
        "seq_len": int(traffic["seq_len"]),
        "loader_gaps_s": cb.loader_gaps,
        "compiles_in_window": cb.compiles_in_window,
        "model_class": config["model"]["class"],
        "model_kwargs": model_kwargs(config),
        "device_kind": jax.devices()[0].device_kind,
    }
