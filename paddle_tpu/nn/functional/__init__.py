"""paddle.nn.functional.

Analog of reference python/paddle/nn/functional/: thin functional layer over
the op library (ops/*), plus attention. Most names are re-exports; the ones
with layer-level semantics (linear, embedding lookup argument order,
attention) are defined here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import ops
from ...ops import (  # noqa: F401 — re-exported op families
    relu, relu6, leaky_relu, prelu, elu, selu, celu, gelu, sigmoid,
    hardsigmoid, hardswish, hardtanh, hardshrink, softshrink, tanhshrink,
    silu, swish, mish, softplus, softsign, softmax, log_softmax, log_sigmoid,
    gumbel_softmax, maxout, thresholded_relu, glu, normalize, tanh,
    conv1d, conv2d, conv3d, conv1d_transpose, conv2d_transpose,
    conv3d_transpose,
    max_pool1d, max_pool2d, max_pool3d, avg_pool2d, avg_pool3d,
    adaptive_avg_pool2d, adaptive_max_pool2d, adaptive_avg_pool3d,
    adaptive_max_pool3d, interpolate, pixel_shuffle, unfold, pad,
    layer_norm, instance_norm, group_norm, rms_norm, local_response_norm,
    dropout, one_hot, embedding as _embedding_op,
    cross_entropy, softmax_with_cross_entropy, nll_loss, mse_loss, l1_loss,
    smooth_l1_loss, binary_cross_entropy, binary_cross_entropy_with_logits,
    sigmoid_cross_entropy_with_logits, kl_div, margin_ranking_loss,
    hinge_embedding_loss, cosine_similarity, label_smooth, square_error_cost,
    log_loss, triplet_margin_loss, huber_loss,
)
from ...ops._dispatch import defop
from ...core.tensor import Tensor

upsample = interpolate


def linear(x, weight, bias=None, name=None):
    """y = x @ W (+ b). Weight is [in, out] (reference nn.functional.common.linear)."""
    out = ops.matmul(x, weight)
    if bias is not None:
        out = ops.add(out, bias)
    return out


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Note the paddle-2.0 argument order (ids first).

    sparse=True in eager mode emits SelectedRows gradients for the table
    (reference lookup_table_v2 grad -> framework/selected_rows.h); under
    jit/static the dense gather's scatter-add transpose is already the
    efficient XLA form, so sparse is a no-op there."""
    if sparse:
        import jax
        from ...core import tape as _tape
        if (_tape.is_grad_enabled() and isinstance(weight, Tensor)
                and not weight.stop_gradient
                and weight._value is not None
                and not isinstance(weight._value, jax.core.Tracer)):
            from ...ops.norm_ops import _sparse_embedding
            return _sparse_embedding(weight, x, padding_idx)
    return _embedding_op(weight, x, padding_idx=padding_idx, sparse=sparse)


def bilinear(x1, x2, weight, bias=None):
    out = ops.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


@defop
def _sdpa(q, k, v, mask, scale, is_causal):
    # q,k,v: [batch, heads, seq, head_dim]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if is_causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        logits = jnp.where(causal, logits, jnp.finfo(logits.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        else:
            logits = logits + mask
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@defop
def _flash_sdpa(q, k, v, mask, scale, is_causal):
    from ...ops.pallas import flash_attention
    bias = None
    if mask is not None:
        # [b,1,1,sk] (bool or additive float) -> additive [b, sk]
        m = mask.reshape(mask.shape[0], mask.shape[-1])
        if m.dtype == jnp.bool_:
            bias = jnp.where(m, 0.0, -1e9).astype(jnp.float32)
        else:
            bias = m.astype(jnp.float32)
    return flash_attention(q, k, v, bias=bias, causal=is_causal, scale=scale)


def _pallas_backend_ok(extra_flag=None):
    """Pallas kernels run compiled on TPU; elsewhere only when an interpret
    flag opts in (tests) or FLAGS_pallas_force_compile is on (AOT TPU
    lowering on a dev box — tools/hlo_evidence.py)."""
    import jax
    from ...core import flags as _flags
    if jax.default_backend() == "tpu":
        return True
    if _flags.flag("FLAGS_pallas_interpret"):
        return True
    if _flags.flag("FLAGS_pallas_force_compile"):
        return True
    return extra_flag is not None and _flags.flag(extra_flag)


def _flash_eligible(query, key, value, attn_mask):
    from ...core import flags as _flags
    from ...ops.pallas import gate_reject
    if not _flags.flag("FLAGS_use_flash_attention"):
        return gate_reject("flash_attention", "flag_off")
    if not _pallas_backend_ok("FLAGS_flash_attention_interpret"):
        return gate_reject("flash_attention", "backend")
    # profitability dispatch (a heuristic: not measured on current code):
    # at short seq XLA's fused attention is expected to win — per-grid-
    # step overhead dominates the kernel; the kernel's O(s) memory +
    # blockwise matmuls pay in the long-context regime.
    # FLAGS_flash_min_seq=0 forces the kernel on.
    min_seq = int(_flags.flag("FLAGS_flash_min_seq"))
    if min_seq and key.shape[-2] < min_seq:
        return gate_reject("flash_attention", "min_seq")
    if attn_mask is not None and isinstance(attn_mask, Tensor) \
            and not attn_mask.stop_gradient:
        # the kernel treats the bias as data (no mask gradient); a learned
        # additive mask must take the jnp path, which differentiates it
        return gate_reject("flash_attention", "mask_grad")
    from ...ops.pallas.flash_attention import supported
    mask_shape = None if attn_mask is None else tuple(attn_mask.shape)
    if not supported(tuple(query.shape), tuple(key.shape),
                     tuple(value.shape), mask_shape):
        return gate_reject("flash_attention", "shape")
    return True


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, scale=None,
                                 training=True):
    """Fused attention core. On TPU this routes through the Pallas
    flash-attention kernel (paddle_tpu.ops.pallas.flash_attention): O(s)
    attention memory, blockwise online softmax on the MXU. The jnp
    reference (_sdpa) covers what the gate rejects: general mask shapes,
    short sequences, non-TPU backends (where XLA fuses the softmax
    chain). An admitted kernel that fails raises."""
    sc = scale if scale is not None else query.shape[-1] ** -0.5
    if _flash_eligible(query, key, value, attn_mask):
        from ...ops.pallas import run_guarded
        out = run_guarded(
            "flash_attention",
            lambda: _flash_sdpa(query, key, value, attn_mask, sc, is_causal))
    else:
        out = _sdpa(query, key, value, attn_mask, sc, is_causal)
    if dropout_p > 0.0 and training:
        out = dropout(out, p=dropout_p, training=True)
    return out


def unfold_linear(*a, **k):  # placeholder parity helper
    raise NotImplementedError


@defop
def _fused_ce_op(hidden, weight, bias, labels, ignore_index):
    from ...ops.pallas.fused_ce import fused_linear_cross_entropy as _k
    return _k(hidden, weight, bias, labels, ignore_index=ignore_index)


@defop
def _ce_head_fallback(hidden, weight, bias, labels, ignore_index):
    # same contract as the kernel: f32 per-token losses, 0 where ignored
    logits = jnp.dot(hidden, weight.T).astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    safe = jnp.where(labels == ignore_index, 0, labels).astype(jnp.int32)
    tgt = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.where(labels == ignore_index, 0.0, lse - tgt)


def fused_linear_cross_entropy(hidden, weight, bias=None, labels=None,
                               ignore_index=-100, reduction="mean"):
    """Cross-entropy of `hidden @ weight^T + bias` against `labels` without
    materializing the [n_tokens, vocab] logits (Pallas kernel on TPU,
    paddle_tpu.ops.pallas.fused_ce). hidden: [..., H] (flattened
    internally); weight: [vocab, H]; labels: [...] int. The usual LM/MLM
    loss head, fused.
    """
    from ...core import flags as _flags
    from ...ops.pallas import gate_reject, run_guarded
    h2 = ops.reshape(hidden, [-1, hidden.shape[-1]])
    y = ops.reshape(labels, [-1])
    n, hd = h2.shape[0], h2.shape[1]
    from ...ops.pallas.fused_ce import supported
    if not _flags.flag("FLAGS_use_fused_ce"):
        use_kernel = gate_reject("fused_ce", "flag_off")
    elif not _pallas_backend_ok():
        use_kernel = gate_reject("fused_ce", "backend")
    elif not supported(n, hd, weight.shape[0]):
        use_kernel = gate_reject("fused_ce", "shape")
    else:
        use_kernel = True
    if use_kernel:
        losses = run_guarded(
            "fused_ce",
            lambda: _fused_ce_op(h2, weight, bias, y, int(ignore_index)))
    else:
        losses = _ce_head_fallback(h2, weight, bias, y, int(ignore_index))
    if reduction == "none":
        return losses
    total = ops.sum(losses)
    if reduction == "sum":
        return total
    valid = ops.sum((y != ignore_index).astype("float32"))
    return total / ops.maximum(valid, ops.ones([], "float32"))


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    """Mask [..., maxlen] with 1 where position < length.

    The mask width is a *shape*, so it must be static under jit: a traced
    `maxlen` (or `maxlen=None` with traced lengths) raises a clear error
    instead of an opaque ConcretizationTypeError mid-trace.
    """
    import jax
    import jax.numpy as jnp
    from ...ops._dispatch import unwrap, wrap
    lv = unwrap(lengths)
    m = unwrap(maxlen) if maxlen is not None else None
    if m is None:
        m = lv.max() if hasattr(lv, "max") else max(lv)
    if isinstance(m, jax.core.Tracer):
        raise ValueError(
            "sequence_mask needs a concrete mask width, but "
            + ("maxlen is a traced value" if maxlen is not None
               else "maxlen=None and `lengths` is traced")
            + "; under jit the output shape must be static — pass a "
              "Python-int maxlen")
    m = int(m)
    mask = jnp.arange(m)[None, :] < lv[..., None]
    from ...core.dtype import to_jax_dtype
    return wrap(mask.astype(to_jax_dtype(dtype)))


# -- round-4: close the functional-surface gap vs the reference ------------
# (python/paddle/nn/functional/__init__.py re-exports the v1 layer names
# too; the implementations live in ops/ — re-export the done ones and
# implement the remaining small kernels below)

from ...ops.math_extra import (affine_grid, diag_embed, grid_sample,  # noqa: E402,F401
                               bilinear_tensor_product, fsp_matrix,
                               filter_by_instag, cvm as continuous_value_model,
                               hash_bucket as hash,  # noqa: A004
                               batch_fc, rank_attention,
                               match_matrix_tensor, conv_shift,
                               gru_unit, lstm_unit, accuracy, auc)
from ...ops.detection import (anchor_generator, bipartite_match, box_clip,  # noqa: E402,F401
                              box_coder, box_decoder_and_assign,
                              collect_fpn_proposals, density_prior_box,
                              distribute_fpn_proposals, iou_similarity,
                              matrix_nms, mine_hard_examples,
                              multiclass_nms, polygon_box_transform,
                              prior_box, roi_align, roi_pool, target_assign,
                              yolo_box, yolov3_loss)
from ...ops.loss import (bpr_loss, center_loss, ctc_loss, hinge_loss,  # noqa: E402,F401
                         hsigmoid_loss, linear_chain_crf, nce, npair_loss,
                         rank_loss, sigmoid_focal_loss,
                         teacher_student_sigmoid_loss,
                         ctc_loss as warpctc, viterbi_decode)
from ...ops.conv import (affine_channel, deform_conv2d,  # noqa: E402,F401
                         deform_conv2d as deformable_conv, im2sequence,
                         psroi_pool, random_crop, row_conv)
from ...ops.norm_ops import data_norm, l2_normalize  # noqa: E402,F401
from ...ops.manipulation import (pad2d, pad3d, pad_constant_like,  # noqa: E402,F401
                                 shuffle_channel, space_to_depth,
                                 temporal_shift)
from ...ops import sequence as _seq  # noqa: E402
# NB: F.sequence_mask stays the jit-aware version defined above — the
# ops.sequence one is eager/RaggedTensor-oriented
from ...ops.sequence import (sequence_concat, sequence_conv,  # noqa: E402,F401
                             sequence_enumerate, sequence_expand,
                             sequence_expand_as, sequence_first_step,
                             sequence_last_step,
                             sequence_pad, sequence_pool, sequence_reshape,
                             sequence_reverse, sequence_scatter,
                             sequence_slice, sequence_softmax,
                             sequence_unpad)


def image_resize(x, out_shape=None, scale=None, resample="BILINEAR",
                 align_corners=True, data_format="NCHW"):
    """v1 alias over interpolate (reference image_resize)."""
    mode = {"BILINEAR": "bilinear", "NEAREST": "nearest",
            "TRILINEAR": "trilinear"}[resample.upper()]
    return interpolate(x, size=out_shape, scale_factor=scale, mode=mode,
                       data_format=data_format)


def resize_bilinear(x, out_shape=None, scale=None, **kw):
    return image_resize(x, out_shape, scale, "BILINEAR")


def resize_nearest(x, out_shape=None, scale=None, **kw):
    return image_resize(x, out_shape, scale, "NEAREST")


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False):
    """reference pool_op.cc 1-D avg (squeeze-through-2D like max_pool1d)."""
    from ... import ops as _ops
    x4 = _ops.unsqueeze(x, [2])
    k = (1, kernel_size if isinstance(kernel_size, int) else kernel_size[0])
    s = (1, (stride if isinstance(stride, int) else
             (stride[0] if stride else k[1])) or k[1])
    p = (0, padding if isinstance(padding, int) else padding[0])
    out = avg_pool2d(x4, k, stride=s, padding=p, ceil_mode=ceil_mode,
                     exclusive=exclusive)
    return _ops.squeeze(out, [2])


def adaptive_avg_pool1d(x, output_size):
    from ... import ops as _ops
    x4 = _ops.unsqueeze(x, [2])
    out = adaptive_avg_pool2d(x4, (1, output_size))
    return _ops.squeeze(out, [2])


def adaptive_max_pool1d(x, output_size):
    from ... import ops as _ops
    x4 = _ops.unsqueeze(x, [2])
    out = adaptive_max_pool2d(x4, (1, output_size))
    return _ops.squeeze(out, [2])


def alpha_dropout(x, p=0.5, training=True):
    """SELU-preserving dropout (reference alpha_dropout): keeps mean/var
    under the SELU fixed point by dropping to alpha' with affine fixup."""
    if not training or p == 0.0:
        return x
    import jax

    from ...core import rng as _rng
    from ...core.tensor import Tensor
    alpha_p = -1.7580993408473766
    v = x._value if isinstance(x, Tensor) else x
    keep = 1.0 - p
    a = (keep + alpha_p ** 2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    mask = jax.random.bernoulli(_rng.next_key(), keep, v.shape)
    out = a * jnp.where(mask, v, alpha_p) + b
    return Tensor(out.astype(v.dtype), _internal=True)


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    """Channel-wise dropout (reference dropout_nd): zero whole feature
    maps."""
    if not training or p == 0.0:
        return x
    import jax

    from ...core import rng as _rng
    from ...core.tensor import Tensor
    v = x._value if isinstance(x, Tensor) else x
    shape = (v.shape[0], v.shape[1], 1, 1) if data_format == "NCHW" \
        else (v.shape[0], 1, 1, v.shape[-1])
    keep = 1.0 - p
    mask = jax.random.bernoulli(_rng.next_key(), keep, shape)
    return Tensor((jnp.where(mask, v, 0) / keep).astype(v.dtype),
                  _internal=True)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    if not training or p == 0.0:
        return x
    import jax

    from ...core import rng as _rng
    from ...core.tensor import Tensor
    v = x._value if isinstance(x, Tensor) else x
    shape = (v.shape[0], v.shape[1], 1, 1, 1) if data_format == "NCDHW" \
        else (v.shape[0], 1, 1, 1, v.shape[-1])
    keep = 1.0 - p
    mask = jax.random.bernoulli(_rng.next_key(), keep, shape)
    return Tensor((jnp.where(mask, v, 0) / keep).astype(v.dtype),
                  _internal=True)


def dice_loss(input, label, epsilon=1e-5):  # noqa: A002
    """reference dice_loss (fluid/layers/loss.py): 1 - 2|X∩Y|/(|X|+|Y|)
    over the class axis (input [N, ..., C] probabilities, label ints)."""
    from ... import ops as _ops
    lab = _ops.one_hot(label.squeeze(-1) if label.shape[-1] == 1 else label,
                       input.shape[-1]).astype(input.dtype)
    reduce_dims = list(range(1, len(input.shape)))
    inter = _ops.sum(input * lab, axis=reduce_dims)
    union = _ops.sum(input, axis=reduce_dims) + _ops.sum(lab,
                                                         axis=reduce_dims)
    return _ops.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


def soft_relu(x, threshold=40.0):
    """reference soft_relu: log(1 + exp(clip(x)))."""
    from ... import ops as _ops
    return _ops.log1p(_ops.exp(_ops.clip(x, -threshold, threshold)))


def add_position_encoding(x, alpha=1.0, beta=1.0):
    """reference add_position_encoding_op.cc: sinusoidal PE added with
    x*alpha + pe*beta; x [B, T, D]."""
    from ...core.tensor import Tensor
    v = x._value if isinstance(x, Tensor) else x
    b, t, d = v.shape
    half = d // 2
    pos = jnp.arange(t, dtype=jnp.float32)[:, None]
    div = jnp.power(10000.0, jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos / div[None, :]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)
    if pe.shape[1] < d:
        pe = jnp.pad(pe, ((0, 0), (0, d - pe.shape[1])))
    out = alpha * v + beta * pe[None].astype(v.dtype)
    return Tensor(out, _internal=True)
