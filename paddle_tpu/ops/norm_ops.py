"""Normalization + dropout + embedding functional ops.

Parity targets: reference operators/batch_norm_op.cc (+ sync_batch_norm_op.cu),
layer_norm_op.cc, instance_norm_op.cc, group_norm_op.cc, dropout_op.cc,
lookup_table_v2_op.cc.

batch_norm is functional: running stats go in and come out as values; the
nn.BatchNorm layer threads them through its buffers so the same op works in
eager mode and inside a jitted/partitioned train step. sync_batch_norm's
cross-device moment reduction (reference sync_batch_norm_op.cu) maps to a
`psum` over the data-parallel mesh axis when inside shard_map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._dispatch import defop
from ..core import rng as _rng


@defop
def layer_norm(x, weight=None, bias=None, epsilon=1e-05, begin_norm_axis=-1):
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim)) \
        if begin_norm_axis != -1 else (x.ndim - 1,)
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


@defop
def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", sync_axis=None):
    """Returns (out, new_running_mean, new_running_var)."""
    c_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = -1

    if training:
        mean = jnp.mean(x, axis=reduce_axes)
        mean_sq = jnp.mean(jnp.square(x), axis=reduce_axes)
        if sync_axis is not None:
            # sync_batch_norm: average moments over the DP mesh axis
            mean = jax.lax.pmean(mean, sync_axis)
            mean_sq = jax.lax.pmean(mean_sq, sync_axis)
        var = mean_sq - jnp.square(mean)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var

    out = (x - jnp.reshape(mean, bshape)) * jax.lax.rsqrt(
        jnp.reshape(var, bshape) + epsilon)
    if weight is not None:
        out = out * jnp.reshape(weight, bshape)
    if bias is not None:
        out = out + jnp.reshape(bias, bshape)
    return out, new_rm, new_rv


@defop
def instance_norm(x, weight=None, bias=None, epsilon=1e-05):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        shape = (1, -1) + (1,) * (x.ndim - 2)
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        shape = (1, -1) + (1,) * (x.ndim - 2)
        out = out + jnp.reshape(bias, shape)
    return out


@defop
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-05,
               data_format="NCHW"):
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    xg = jnp.reshape(x, (n, num_groups, c // num_groups) + spatial)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = jnp.reshape((xg - mean) * jax.lax.rsqrt(var + epsilon), x.shape)
    if weight is not None:
        shape = (1, -1) + (1,) * (x.ndim - 2)
        out = out * jnp.reshape(weight, shape)
    if bias is not None:
        shape = (1, -1) + (1,) * (x.ndim - 2)
        out = out + jnp.reshape(bias, shape)
    return out


@defop
def rms_norm(x, weight=None, epsilon=1e-06):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight
    return out


def _keep_mask_global(key, keep, shape):
    """Bernoulli(keep) mask via the TPU hardware bit generator.

    The per-call key still comes from the threefry chain (statistically
    independent across calls); only the BULK bit generation is re-seated on
    an unsafe_rbg key so XLA lowers it to RngBitGenerator — a hardware
    instruction — instead of a threefry hash per element, and the comparison
    is uint32-vs-uint32 so no (x64-widened) float uniforms are materialized.
    Against jax.random.bernoulli on a threefry key, the draw and its
    `where` alone on one TPU v5 lite, bf16 x, keep 0.9 (my chip run,
    PR 44): [128, 128, 768] 0.243 against 0.659 ms, [128, 128, 3072] 0.970
    against 2.464 ms; the 49 sites of a BERT-base step at b 128: 20.7
    against 54.2 ms."""
    kd = jax.random.key_data(key).astype(jnp.uint32).ravel()
    words = jnp.concatenate([kd, kd ^ jnp.uint32(0x9E3779B9)])[:4]
    rbg_key = jax.random.wrap_key_data(words, impl="unsafe_rbg")
    thresh = jnp.uint32(int(keep * 0xFFFFFFFF))
    return jax.random.bits(rbg_key, shape, jnp.uint32) < thresh


def _local_draw_plan(key, shape):
    """(mesh to hand shard_map, its manual axes, dp) for a draw under the
    `dp` axis, or None where the draw stays global. GSPMD does not
    partition RngBitGenerator: in a step partitioned along `dp` every
    device would make the GLOBAL batch's bits and slice its rows out.
    Engages only where a traced draw sees a mesh whose `dp` is wider than
    1; eager and no-mesh draws count nothing, the two refusals count by
    reason (once a dropout site a trace)."""
    if not isinstance(key, jax.core.Tracer):
        return None
    from ..distributed import mesh as _mesh
    ctx = jax.sharding.get_abstract_mesh()
    manual = frozenset(ctx.manual_axes)
    # inside a shard_map the enclosing mesh is the one in force
    mesh = ctx if manual else _mesh.get_mesh()
    dp = 1 if mesh is None else mesh.shape.get("dp", 1)
    if dp < 2:
        return None
    from ..core import monitor as _monitor
    if _mesh.in_spmd_region("dp"):
        # LocalSGD, pipeline and MoE programs: the rows are local already
        _monitor.stat_add("dropout.local_draw_fallback.manual")
        return None
    if not shape or shape[0] % dp:
        _monitor.stat_add("dropout.local_draw_fallback.indivisible")
        return None
    _monitor.stat_add("dropout.local_draw")
    # a nested shard_map takes the context's mesh and names the axes that
    # are manual already beside its own
    return (None if manual else mesh), manual | {"dp"}, dp


@functools.lru_cache(maxsize=64)
def _local_drawer(mesh, axes, keep, local):
    """The draw of one `dp` shard's rows, jitted once a (mesh, keep, local
    shape): a step's sites share a few shapes (BERT-base: 49 sites, three
    shapes), so the step traces and lowers a few draws, not one a site."""
    from jax.sharding import PartitionSpec as P
    from ..distributed import mesh as _mesh

    def draw(k):
        k = jax.random.fold_in(k, jax.lax.axis_index("dp"))
        return _keep_mask_global(k, keep, local)

    return jax.jit(_mesh.shard_map(draw, mesh=mesh, in_specs=P(),
                                   out_specs=P("dp"), axis_names=axes))


def _keep_mask(key, keep, shape):
    """Bernoulli(keep) mask of `shape`. In a trace under a mesh whose `dp`
    axis is wider than 1 each `dp` shard draws the bits of its own rows
    (`fold_in(key, axis_index("dp"))`, the other axes left to GSPMD), so a
    batch-sharded step generates a chip's share and not the global batch
    on every chip; which bits a row gets then depends on the mesh, as it
    already did under LocalSGD. Everywhere else: `_keep_mask_global`."""
    plan = _local_draw_plan(key, shape)
    if plan is None:
        return _keep_mask_global(key, keep, shape)
    mesh, axes, dp = plan
    local = (shape[0] // dp,) + tuple(shape[1:])
    return _local_drawer(mesh, axes, keep, local)(key)


@defop(name="dropout_op")
def _dropout(x, p, mode):
    # the key is drawn INSIDE the kernel so that recorded static Programs
    # and jitted steps split it from the per-run chain (core/rng.py) rather
    # than baking one mask at record time
    keep = 1.0 - p
    if keep <= 0.0:  # p=1: drop everything (valid per reference dropout_op)
        return jnp.zeros_like(x)
    key = _rng.next_key()
    mask = _keep_mask(key, keep, x.shape)
    if mode == "upscale_in_train":
        scale = jnp.asarray(1.0 / keep, x.dtype)
        return jnp.where(mask, x * scale, jnp.zeros((), x.dtype))
    return jnp.where(mask, x, jnp.zeros((), x.dtype))


def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None):
    if not training or p == 0.0:
        return x
    return _dropout(x, p=float(p), mode=mode)


@defop
def embedding(weight, ids, padding_idx=None, sparse=False):
    # reference: operators/lookup_table_v2_op.cc. In jitted steps the dense
    # gather is the right form (XLA fuses the scatter-add transpose); in
    # EAGER mode sparse=True emits SelectedRows grads so huge-vocab tables
    # never materialize dense gradients (core/selected_rows.py).
    out = jnp.take(weight, ids, axis=0)
    if padding_idx is not None:
        if padding_idx < 0:  # paddle normalizes negative indices
            padding_idx = weight.shape[0] + padding_idx
        mask = (ids != padding_idx)[..., None].astype(out.dtype)
        out = out * mask
    return out


def _sparse_embedding(weight_t, ids_t, padding_idx):
    """Eager-only sparse-grad embedding: custom tape Node whose backward
    emits SelectedRows for the table (the lookup_table_v2 grad kernel's
    SelectedRows output, made a tape citizen)."""
    from ..core.selected_rows import SelectedRows
    from ..core.tape import Node, _wrap_outputs
    from ..core.tensor import Tensor

    weight = weight_t._value
    ids = ids_t._value if isinstance(ids_t, Tensor) else jnp.asarray(ids_t)
    pidx = padding_idx
    if pidx is not None and pidx < 0:
        pidx = weight.shape[0] + pidx
    out = jnp.take(weight, ids, axis=0)
    if pidx is not None:
        out = out * (ids != pidx)[..., None].astype(out.dtype)

    def vjp_fn(g):
        rows = ids.reshape(-1)
        vals = g.reshape(-1, weight.shape[-1]).astype(weight.dtype)
        if pidx is not None:
            keep = (rows != pidx)[:, None].astype(vals.dtype)
            vals = vals * keep
        return (SelectedRows(rows, vals, weight.shape),)

    node = Node(vjp_fn, [weight_t], [(tuple(out.shape), out.dtype)],
                "embedding_sparse_grad", False)
    return _wrap_outputs(out, node=node, stop_gradient=False)


@defop
def local_response_norm(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    c = x.shape[1]
    half = size // 2
    padded = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2))
    acc = jnp.zeros_like(x)
    for i in range(size):
        acc = acc + padded[:, i:i + c]
    return x / jnp.power(k + alpha * acc, beta)


# -- round-4 widening ------------------------------------------------------

@defop
def data_norm(x, batch_size, batch_sum, batch_square_sum, epsilon=1e-4):
    """reference data_norm_op.cc (CTR models): normalize by accumulated
    batch statistics; means = batch_sum/batch_size, scales =
    sqrt(batch_size / batch_square_sum_centered)."""
    means = batch_sum / batch_size
    var = batch_square_sum / batch_size - jnp.square(means)
    scales = 1.0 / jnp.sqrt(var + epsilon)
    return (x - means) * scales


@defop
def l2_normalize(x, axis=-1, epsilon=1e-12):
    """reference norm_op.cc (l2 normalize along axis)."""
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return x / jnp.maximum(n, epsilon)


def lrn(x, n=5, k=1.0, alpha=1e-4, beta=0.75, data_format="NCHW"):
    """reference lrn_op.cc — v1 name for local_response_norm (NCHW)."""
    return local_response_norm(x, size=n, alpha=alpha, beta=beta, k=k)
