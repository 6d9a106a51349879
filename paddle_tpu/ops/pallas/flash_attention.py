"""Flash attention — online-softmax attention as a Pallas TPU kernel.

TPU-native replacement for the unfused softmax(QK^T)V chain: the reference
hand-writes its attention-adjacent kernels in CUDA/xbyak
(reference operators/math/softmax.cu, operators/jit/gen/jitcode.h:23,
operators/fused/multihead_matmul_op.cu); on TPU the equivalent tier is
Pallas. The kernel never materializes the [s_q, s_k] score matrix in HBM —
scores live blockwise in VMEM with f32 running max/sum accumulators, so
attention memory is O(s) and both matmuls hit the MXU in bf16 with f32
accumulation.

Forward and backward are separate kernels wired through jax.custom_vjp (the
analog of the reference's hand-written *_grad kernels): backward recomputes
scores blockwise from the saved logsumexp, FlashAttention-2 style.

Layout: q, k, v are [batch*heads, seq, head_dim]; an optional additive bias
[batch, s_k] implements padding masks; `causal=True` adds the triangular
mask in-kernel. Runs compiled on TPU, interpreted elsewhere (CPU mesh
tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np

_Z = np.int32(0)  # index-map zero: a Python literal 0 traces as i64 under
                  # jax_enable_x64 and Mosaic rejects i64 index returns
                  # (numpy scalar, not jnp — index maps may not capture
                  # constant Arrays)

NEG_INF = -1e9  # finite "masked" value: keeps running-max finite even for
                # fully-padded rows (exp(NEG_INF - NEG_INF) stays sane)


def _pick_block(s: int, target: int = None, flag: str = None):
    """Largest block size <= target that divides s, no smaller than 8 (the
    f32 sublane tile); None means "not kernel-friendly, use the jnp path".
    target=None: FLAGS_flash_block_* override, else auto — 256 once the
    sequence is long enough to amortize the grid (a heuristic: not
    measured on current code)."""
    if target is None:
        cfg = 0
        if flag is not None:
            from ...core import flags as _flags
            cfg = int(_flags.flag(flag) or 0)
        target = cfg if cfg else (256 if s >= 1024 else 128)
    for b in (target, 512, 256, 128, 64, 32, 16, 8):
        if b <= target and s % b == 0:
            return b
    return None


def block_candidates(s: int, cap: int = 512):
    """Block sizes worth autotuning over: divisors of s in [64, cap] (below
    64 the grid overhead always loses on the MXU), plus the sublane floor
    when s is tiny."""
    cands = [b for b in (512, 256, 128, 64) if b <= cap and s % b == 0]
    if not cands:
        cands = [b for b in (32, 16, 8) if s % b == 0][:1]
    return cands


def _ceil_to(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def _interpret() -> bool:
    """Pallas execution mode. Compiled on TPU; interpreted elsewhere —
    except under FLAGS_pallas_force_compile, which forces Mosaic lowering
    even off-TPU so tools/hlo_evidence.py can AOT-lower the bench graphs
    for a TPU target on any dev box (lowering needs no TPU; only *running*
    does)."""
    from ...core import flags as _flags
    if _flags.flag("FLAGS_pallas_force_compile"):
        return False
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _causal_live(iq, ik, bq, bk, off):
    """Is block (iq, ik) at least partly unmasked under bottom-right-aligned
    causal masking (col <= row + off, off = s_k - s_q, matching _sdpa)?"""
    return ik * bk <= iq * bq + (bq - 1) + off


def _causal_mask(s, iq, ik, bq, bk, off):
    row = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # np.float32 scalar, not the weak python float: a weak-f64 scalar
    # convert inside a kernel recurses Mosaic's lowering on some jax
    # builds (and 64-bit kernel values SIGABRT on TPU regardless)
    return jnp.where(row + off >= col, s, np.float32(NEG_INF))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                      m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, nk,
                      off):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]                               # [bq, d]
        k = k_ref[0]                               # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0]                    # [1, bk] broadcasts
        if causal:
            s = _causal_mask(s, iq, ik, bq, bk, off)

        m_prev = m_scr[:]                          # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        p = jnp.exp(s - m_new)                     # [bq, bk] f32
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    if causal:
        pl.when(_causal_live(iq, ik, bq, bk, off))(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _flush():
        denom = jnp.maximum(l_scr[:], 1e-30)       # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:] + jnp.log(denom)).reshape(lse_ref.shape)


def _fwd(q, k, v, bias, scale, causal, heads, bq, bk, off):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // bq, sk // bk
    grid = (bh, nq, nk)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda ib, iq, ik: (ib, iq, _Z)),
        pl.BlockSpec((1, bk, d), lambda ib, iq, ik: (ib, ik, _Z)),
        pl.BlockSpec((1, bk, d), lambda ib, iq, ik: (ib, ik, _Z)),
    ]
    args = [q, k, v]
    if bias is not None:
        # [b*h, 1, sk]: tiled per head so the index map is pure indexing
        # (arithmetic like ib // heads recurses in this jax's index-map
        # tracing), and the singleton row keeps the block's sublane dim
        # equal to the array's (TPU blocks must be (8,128)-divisible or
        # full-dim)
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda ib, iq, ik: (ib, _Z, ik)))
        args.append(jnp.repeat(
            bias.reshape(bias.shape[0], 1, bias.shape[-1]), heads, axis=0))

    # `off` is the causal-diagonal alignment of the ORIGINAL (pre-padding)
    # shapes — sk_orig - sq_orig — so tile padding can't shift the mask
    opts = dict(scale=scale, causal=causal, bq=bq, bk=bk, nk=nk, off=off)
    if bias is not None:
        kernel = functools.partial(_flash_fwd_kernel, **opts)
    else:
        def kernel(qr, kr, vr, o, lse, m, l, a):  # noqa: E741
            return _flash_fwd_kernel(qr, kr, vr, None, o, lse, m, l, a,
                                     **opts)
        # the closure's name is the `kernel_name` stamped into the lowered
        # tpu_custom_call — tools/hlo_evidence.py greps for it
        kernel.__name__ = _flash_fwd_kernel.__name__

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda ib, iq, ik: (ib, iq, _Z)),
            # lse rides as [bh, sq, 1]: trailing singleton == array dim, and
            # the sublane dim bq is 8-divisible — legal TPU tiling, unlike a
            # (1, bq) block over [bh, sq]
            pl.BlockSpec((1, bq, 1), lambda ib, iq, ik: (ib, iq, _Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            _vmem((bq, 1), jnp.float32),
            _vmem((bq, 1), jnp.float32),
            _vmem((bq, d), jnp.float32),
        ],
        compiler_params=_cparams("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
    )(*args)
    return out, lse[..., 0]


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _cparams(*semantics):
    """Mosaic grid semantics: 'parallel' dims can be reordered/pipelined by
    the compiler, 'arbitrary' marks the sequential reduction dim (the
    revisiting accumulator pattern). Without this Mosaic assumes every dim
    is arbitrary and cannot overlap the next block's DMA with compute."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, scale, causal, bq, bk,
                         nk, off):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        if causal:
            s = _causal_mask(s, iq, ik, bq, bk, off)

        lse = lse_ref[:].reshape(bq, 1)
        p = jnp.exp(s - lse)                        # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[:].reshape(bq, 1)
        ds = p * (dp - delta) * scale               # [bq, bk] f32
        dq_scr[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_live(iq, ik, bq, bk, off))(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _flush():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                          *, scale, causal, bq, bk, nq, off):
    ik, iq = pl.program_id(1), pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0]
        if causal:
            s = _causal_mask(s, iq, ik, bq, bk, off)

        lse = lse_ref[:].reshape(bq, 1)
        p = jnp.exp(s - lse)                        # [bq, bk] f32
        # dv += P^T dO   (contract over bq)
        dv_scr[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[:].reshape(bq, 1)
        ds = p * (dp - delta) * scale
        # dk += dS^T Q   (contract over bq)
        dk_scr[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    if causal:
        pl.when(_causal_live(iq, ik, bq, bk, off))(_compute)
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _flush():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, bias, out, lse, do, scale, causal, heads, bq, bk, off):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // bq, sk // bk
    # lse/delta ride as [bh, sq, 1] and bias as [b, 1, sk] for legal TPU
    # block tiling (see _fwd)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[..., None]             # [bh, sq, 1]
    lse3 = lse[..., None]                           # [bh, sq, 1]
    bias3 = None if bias is None else jnp.repeat(
        bias.reshape(bias.shape[0], 1, bias.shape[-1]), heads, axis=0)

    def specs(extra_bias):
        base = [
            pl.BlockSpec((1, bq, d), lambda ib, i, j: (ib, i, _Z)),   # q
            pl.BlockSpec((1, bk, d), lambda ib, i, j: (ib, j, _Z)),   # k
            pl.BlockSpec((1, bk, d), lambda ib, i, j: (ib, j, _Z)),   # v
        ]
        if extra_bias:
            base.append(pl.BlockSpec(
                (1, 1, bk), lambda ib, i, j: (ib, _Z, j)))
        base += [
            pl.BlockSpec((1, bq, d), lambda ib, i, j: (ib, i, _Z)),   # do
            pl.BlockSpec((1, bq, 1), lambda ib, i, j: (ib, i, _Z)),   # lse
            pl.BlockSpec((1, bq, 1), lambda ib, i, j: (ib, i, _Z)),   # delta
        ]
        return base

    args = ([q, k, v, bias3] if bias is not None else [q, k, v]) \
        + [do, lse3, delta]

    # ---- dq: grid (bh, nq, nk), k-blocks innermost -----------------------
    dq_kernel = functools.partial(_flash_bwd_dq_kernel, scale=scale,
                                  causal=causal, bq=bq, bk=bk, nk=nk,
                                  off=off)
    if bias is None:
        inner_dq = dq_kernel

        def dq_kernel(qr, kr, vr, dor, lser, dr, dqr, scr):  # noqa: F811
            return inner_dq(qr, kr, vr, None, dor, lser, dr, dqr, scr)
        dq_kernel.__name__ = _flash_bwd_dq_kernel.__name__

    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, nq, nk),
        in_specs=specs(bias is not None),
        out_specs=pl.BlockSpec((1, bq, d), lambda ib, i, j: (ib, i, _Z)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[_vmem((bq, d), jnp.float32)],
        compiler_params=_cparams("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
    )(*args)

    # ---- dk/dv: grid (bh, nk, nq), q-blocks innermost --------------------
    def specs_kv(extra_bias):
        base = [
            pl.BlockSpec((1, bq, d), lambda ib, i, j: (ib, j, _Z)),   # q
            pl.BlockSpec((1, bk, d), lambda ib, i, j: (ib, i, _Z)),   # k
            pl.BlockSpec((1, bk, d), lambda ib, i, j: (ib, i, _Z)),   # v
        ]
        if extra_bias:
            base.append(pl.BlockSpec(
                (1, 1, bk), lambda ib, i, j: (ib, _Z, i)))
        base += [
            pl.BlockSpec((1, bq, d), lambda ib, i, j: (ib, j, _Z)),   # do
            pl.BlockSpec((1, bq, 1), lambda ib, i, j: (ib, j, _Z)),   # lse
            pl.BlockSpec((1, bq, 1), lambda ib, i, j: (ib, j, _Z)),   # delta
        ]
        return base

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                                   causal=causal, bq=bq, bk=bk, nq=nq,
                                   off=off)
    if bias is None:
        inner_dkv = dkv_kernel

        def dkv_kernel(qr, kr, vr, dor, lser, dr, dkr, dvr, ks, vs):  # noqa: F811,E501
            return inner_dkv(qr, kr, vr, None, dor, lser, dr, dkr, dvr,
                             ks, vs)
        dkv_kernel.__name__ = _flash_bwd_dkv_kernel.__name__

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, nk, nq),
        in_specs=specs_kv(bias is not None),
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda ib, i, j: (ib, i, _Z)),
            pl.BlockSpec((1, bk, d), lambda ib, i, j: (ib, i, _Z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[_vmem((bk, d), jnp.float32),
                        _vmem((bk, d), jnp.float32)],
        compiler_params=_cparams("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
    )(*args)
    return dq, dk, dv


# --------------------------------------------------------------------------
# public op (custom_vjp)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, bias, scale, causal, heads, bq, bk, off):
    out, _ = _fwd(q, k, v, bias, scale, causal, heads, bq, bk, off)
    return out


def _flash_fwd(q, k, v, bias, scale, causal, heads, bq, bk, off):
    out, lse = _fwd(q, k, v, bias, scale, causal, heads, bq, bk, off)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(scale, causal, heads, bq, bk, off, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv = _bwd(q, k, v, bias, out, lse, g, scale, causal, heads,
                      bq, bk, off)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_with_lse(q, k, v, bias, scale, causal, heads, bq, bk, off):
    return _fwd(q, k, v, bias, scale, causal, heads, bq, bk, off)


def _flash_with_lse_fwd(q, k, v, bias, scale, causal, heads, bq, bk, off):
    out, lse = _fwd(q, k, v, bias, scale, causal, heads, bq, bk, off)
    return (out, lse), (q, k, v, bias, out, lse)


def _flash_with_lse_bwd(scale, causal, heads, bq, bk, off, res, g):
    q, k, v, bias, out, lse = res
    g_out, _g_lse = g  # lse is a statistic; cotangents through it are
    # not propagated (ring merges treat it as weighting data)
    dq, dk, dv = _bwd(q, k, v, bias, out, lse, g_out, scale, causal, heads,
                      bq, bk, off)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def supported(q_shape, k_shape, v_shape, mask_shape=None) -> bool:
    """Static predicate: can flash_attention handle these shapes? Anything
    rejected here must take the jnp fallback (_sdpa), which handles general
    broadcasting. Sequence lengths are unconstrained: the wrapper pads
    q/k/v to (8,128)-tile-friendly multiples of 8 and slices the output
    back, so ragged lengths are kernel-eligible too."""
    if len(q_shape) != 4 or len(k_shape) != 4 or len(v_shape) != 4:
        return False
    b, h, sq, d = q_shape
    sk = k_shape[2]
    if d > 256 or k_shape[3] != d or v_shape[3] != d or v_shape[2] != sk:
        return False
    if sq < 1 or sk < 1:
        return False
    if mask_shape is not None:
        # exactly [b, 1, 1, sk]: the kernel's bias path does no broadcasting
        if tuple(mask_shape) != (b, 1, 1, sk):
            return False
    return True


def _pick_blocks(sq, sk, d, dtype, causal, with_bias, measure_builder):
    """Resolve (bq, bk): explicit FLAGS_flash_block_* overrides win, then
    the autotune table (ops/pallas/autotune.py), then the static
    heuristic. sq/sk are already tile-padded (multiples of 8)."""
    from ...core import flags as _flags
    from . import autotune
    cfg_q = int(_flags.flag("FLAGS_flash_block_q") or 0)
    cfg_k = int(_flags.flag("FLAGS_flash_block_k") or 0)
    default = (_pick_block(sq, cfg_q or None),
               _pick_block(sk, cfg_k or None))
    if cfg_q or cfg_k:
        return default
    cands = [(bq, bk) for bq in block_candidates(sq)
             for bk in block_candidates(sk)]
    return autotune.lookup(
        "flash_fwd",
        (autotune.bucket(sq), autotune.bucket(sk), d, int(bool(causal)),
         int(with_bias)),  # the bias operand changes per-block VMEM traffic
        dtype, cands, measure_builder(), default)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    return_lse=False):
    """Online-softmax attention, O(s) memory.

    q: [b, h, s_q, d]; k, v: [b, h, s_k, d]; bias: optional additive mask
    [b, s_k] (f32; use NEG_INF-scale values for masked keys — treated as
    non-differentiable data). Returns [b, h, s_q, d] in q's dtype; with
    return_lse=True also the per-row logsumexp [b, h, s_q] (f32), which
    lets callers merge partial-attention blocks exactly — the ring
    attention merge (distributed/ring_attention.py).

    Ragged lengths are handled here, not by the caller: q/k/v are padded
    up to a multiple of 8 (f32 sublane tile), padded key columns are
    masked through the bias, and the output is sliced back — the docstring
    contract is "any 4-D shape with matching head dims either runs the
    kernel or falls back", never a ValueError about padding.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if k.shape[3] != d or v.shape[3] != d or v.shape[2] != sk:
        raise ValueError(
            f"flash_attention needs matching head_dim/seq for k and v; got "
            f"q{tuple(q.shape)} k{tuple(k.shape)} v{tuple(v.shape)}")
    if scale is None:
        scale = d ** -0.5
    off = sk - sq  # causal alignment of the ORIGINAL shapes
    # padded key columns must never win the softmax: mask via bias —
    # except under causal with no bias, where the original-shape
    # diagonal (off = sk - sq) already caps every real row at
    # col <= sk-1, so manufacturing a bias would only add the
    # per-head bias materialization and kernel loads for nothing
    with_bias = bias is not None or (not causal and sk % 8 != 0)
    # the bias rides in (1, 1, bk) blocks whose lane dim Mosaic wants
    # 128-divisible, so with a bias keys pad to the lane tile, not just
    # the 8-row sublane tile
    sq_p, sk_p = _ceil_to(sq, 8), _ceil_to(sk, 128 if with_bias else 8)
    if bias is not None:
        bias = bias.astype(jnp.float32)
    if sk_p != sk:
        if with_bias:
            if bias is None:
                bias = jnp.zeros((b, sk), jnp.float32)
            bias = jnp.pad(bias, ((0, 0), (0, sk_p - sk)),
                           constant_values=NEG_INF)
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    if sq_p != sq:
        # padded query rows compute garbage rows that are sliced off below
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    qf = q.reshape(b * h, sq_p, d)
    kf = k.reshape(b * h, sk_p, d)
    vf = v.reshape(b * h, sk_p, d)
    if bias is not None:
        bias = jax.lax.stop_gradient(bias)

    def measure_builder():
        # synthetic concrete inputs of the call's shape/dtype: the real
        # q/k/v are usually tracers (this runs mid-jit), and TPU matmul
        # timing is data-independent, so zeros measure the same kernel
        def measure(params):
            from . import autotune
            bq_, bk_ = params
            qz = jnp.zeros((b * h, sq_p, d), q.dtype)
            kz = jnp.zeros((b * h, sk_p, d), k.dtype)
            vz = jnp.zeros((b * h, sk_p, d), v.dtype)
            bz = None if bias is None else jnp.zeros((b, sk_p), jnp.float32)
            fn = jax.jit(lambda a, b_, c: _flash(
                a, b_, c, bz, float(scale), bool(causal), h, bq_, bk_, off))
            return autotune.time_thunk(lambda: fn(qz, kz, vz))
        return measure

    bq, bk = _pick_blocks(sq_p, sk_p, d, str(q.dtype), causal,
                          bias is not None, measure_builder)
    if return_lse:
        out, lse = _flash_with_lse(qf, kf, vf, bias, float(scale),
                                   bool(causal), h, bq, bk, off)
        out = out.reshape(b, h, sq_p, d)
        lse = lse.reshape(b, h, sq_p)
        if sq_p != sq:
            out, lse = out[:, :, :sq], lse[:, :, :sq]
        return out, lse
    out = _flash(qf, kf, vf, bias, float(scale), bool(causal), h, bq, bk,
                 off)
    out = out.reshape(b, h, sq_p, d)
    return out[:, :, :sq] if sq_p != sq else out
