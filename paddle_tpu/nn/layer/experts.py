"""A sparse expert layer that is told which experts it holds.

`distributed/moe.py` is the training-side Switch layer: top-1, softmax,
a capacity past which tokens are dropped, experts sharded over a mesh
axis. This is the layer that serving runs (DeepSeek-V3 / Kimi-K2 style):

- **router over every expert**: float32 scores, `num_experts` (+
  `zero_experts`) wide, sigmoid (DeepSeek-V3 / Kimi-K2) or a softmax over
  the whole width (LongCat-Flash: `score_func="softmax"`); the `top_k`
  experts of a token are those with the highest `score + bias` (the
  load-balancing bias, `e_score_correction_bias`), its weights are the
  scores themselves, normalised over the chosen `top_k`
  (`norm_topk_prob`, the default) or left as they are, and scaled by
  `routed_scaling_factor`;
- **zero-compute experts**: `zero_experts = n` ids past the routed ones,
  `num_experts .. num_experts + n - 1`, routed over by the same top-k.
  Such an expert is the identity on the layer's input: it has no
  weights, nobody holds it, and what a token's zero experts add is one
  multiply-add, `(sum of their weights) * x`, computed here in full for
  this chip's tokens (on a deployment a token's home chip computes it).
  So the products a token costs vary inside one batch;
- **a held share**: `held = (first, count)` names the contiguous range of
  experts whose weights live here — one chip's share of an
  expert-parallel deployment. Routing, top-k and the normalisation are
  over all `num_experts`; the sum runs over the chosen experts that are
  held. What the absent experts would add is left out (on a deployment
  it arrives through the exchange between chips; here nothing stands in
  for it). The shared expert is computed in full. With `held` = all the
  layer is the whole layer;
- **dropless, static shapes**: no capacity. The (token, expert) pairs
  that are held are sorted by expert and cut into row blocks of
  `block_rows(tokens)` rows, each block of one expert; a loop runs over
  the blocks IN USE, so the matrix products cost what the routing asks
  for, whatever it asks. The rule that bounds the loop: with `T` tokens,
  `P = T * top_k` pairs at most are held, an expert's rows are padded to
  a whole block, so at most `ceil(P / M) + count` blocks of `M` rows
  exist; the expectation is `T * top_k * count / num_experts` pairs;
- **two forms of the blocks' products, one arithmetic**. The plain form
  (`_routed_expert_ffn`) is a `fori_loop` over the blocks in use: a pass
  slices its expert's three matrices and multiplies, and since XLA
  pipelines nothing across the passes of a `while`, every pass starts
  its weight streams from nothing. The grouped form
  (`_grouped_expert_ffn` -> `ops/pallas/grouped_ffn.py`) is ONE Pallas
  kernel a layer: grid (blocks, tiles of the expert width), a block's
  expert's tiles picked through scalar prefetch from the same order of
  the pairs (made by counting there, not by a sort), so the next tile,
  of this expert or the next, is fetched under the current product; x
  and y stay whole in VMEM and a block's rows are gathered and summed
  back by exact one-hot products on the MXU. bf16 operands, float32
  accumulation and weights in both; only the order in which a token's
  pairs are summed differs. The gate (`_grouped_kernel_eligible`) reads
  what the call shows and nothing else: a backend that runs Pallas (a
  TPU, or `FLAGS_pallas_interpret` for the CPU tests), bf16, `H` and `I`
  whole lane tiles, at most 512 tokens (a block's work on y grows with
  the tokens; past that the loop wins on the chip), and a cut that fits
  VMEM with x and y whole; what it rejects
  (`pallas.gate_reject.grouped_expert_ffn.{backend,dtype,shape,tokens,
  vmem}`) takes the plain form, which is also the kernel's parity
  oracle.

`routed` also returns how many pairs each held expert got and, over the
whole router (held or not), how many of the tokens' pairs fell on routed
experts and how many on zero-compute experts, which is what the serving
counters (`serve.moe_*`) are made of.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import initializer as I
from .layers import Layer

__all__ = ["RoutedExperts"]


def block_rows(tokens):
    """Rows of one block of the grouped expert products: the MXU's 128
    at prefill sizes, the whole batch (to a 16-row bf16 tile) below."""
    return min(128, -(-int(tokens) // 16) * 16)


def _swiglu(x, gate, up, down):
    g = jnp.dot(x, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, up, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    return jnp.dot(a, down, preferred_element_type=jnp.float32)


# jitted under a name of its own, so that a device trace can tell the
# routed products from the rest of a serve program
@jax.jit
def _routed_expert_ffn(x, idx, weights, valid, gate, up, down, first):
    """Σ over the held experts a token chose of weight * SwiGLU_e(x).
    x [T, H]; idx [T, K] i32 expert ids over the router's width; weights
    [T, K] f32; valid [T] bool (a pad row routes nowhere); gate/up
    [n, H, I], down [n, I, H]: experts first..first+n-1.
    -> (y [T, H] f32, pairs per held expert [n] i32).
    The plain form: a `while` over the row blocks in use, three slices
    and three products a pass."""
    T, K = idx.shape
    n = gate.shape[0]
    M = block_rows(T)
    P = T * K
    local = idx - jnp.asarray(first, jnp.int32)
    held = (local >= 0) & (local < n) & valid[:, None]
    key = jnp.where(held, local, n).reshape(P)             # n = not here
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = jnp.concatenate([order, jnp.zeros((M,), jnp.int32)])
    counts = jnp.sum(key[:, None] == jnp.arange(n, dtype=jnp.int32)[None],
                     axis=0, dtype=jnp.int32)              # [n]
    blocks = (counts + (M - 1)) // M                       # per expert
    last_block = jnp.cumsum(blocks)
    group_start = jnp.cumsum(counts) - counts              # in sorted order
    w_flat = weights.reshape(P)
    lane = jnp.arange(M, dtype=jnp.int32)

    def one_block(j, y):
        j = jnp.asarray(j, jnp.int32)
        e = jnp.minimum(jnp.searchsorted(last_block, j, side="right"),
                        n - 1).astype(jnp.int32)
        r0 = (j - (last_block[e] - blocks[e])) * M
        pairs = jax.lax.dynamic_slice(order, (group_start[e] + r0,), (M,))
        live = lane < counts[e] - r0
        tok = jnp.where(live, pairs // K, 0)
        w = jnp.where(live, w_flat[pairs], 0.0)
        out = _swiglu(x[tok], gate[e], up[e], down[e])
        return y.at[tok].add(out * w[:, None])

    y = jax.lax.fori_loop(0, last_block[-1], one_block,
                          jnp.zeros(x.shape, jnp.float32))
    return y, counts


@jax.jit
def _grouped_expert_ffn(x, idx, weights, valid, gate, up, down, first):
    """`_routed_expert_ffn` with the blocks' products as one Pallas kernel
    (ops/pallas/grouped_ffn.py). Array code here, for the static bound of
    `ceil(P / M) + n` blocks: where each held pair sits in the blocks'
    order, by counting (a pair's rank among its expert's pairs, in token
    order as the stable sort gives it: no sort and no gather), the
    counts, and every block's expert; the kernel gathers, multiplies and
    sums."""
    from ...ops.pallas.grouped_ffn import grouped_ffn, grouped_ffn_blocks
    T, K = idx.shape
    n = gate.shape[0]
    M = block_rows(T)
    local = idx - jnp.asarray(first, jnp.int32)
    held = (local >= 0) & (local < n) & valid[:, None]
    mine = (held[..., None] & (local[..., None] == jnp.arange(
        n, dtype=jnp.int32))).reshape(T * K, n).astype(jnp.int32)
    upto = jnp.cumsum(mine, axis=0)            # pairs of expert e so far
    counts = upto[-1]                                          # [n]
    blocks = (counts + (M - 1)) // M                       # per expert
    last_block = jnp.cumsum(blocks)
    n_live = last_block[-1]
    # a pair's row: its expert's first block's first row + its rank
    pair_row = jnp.sum(mine * (upto - 1 + ((last_block - blocks) * M)[None]),
                       axis=1).reshape(T, K)
    j = jnp.arange(grouped_ffn_blocks(T, K, n, M), dtype=jnp.int32)
    # a block not in use names the last live block's expert: its index
    # repeats and nothing is fetched for it
    e = jnp.minimum(jnp.searchsorted(
        last_block, jnp.minimum(j, jnp.maximum(n_live - 1, 0)),
        side="right"), n - 1).astype(jnp.int32)
    y = grouped_ffn(x, jnp.where(held, pair_row, -1), weights, e, n_live,
                    gate, up, down, rows=M)
    return y, counts


def _grouped_kernel_eligible(x, gate):
    """The grouped kernel's gate, by what the call shows: a backend that
    runs Pallas, bf16 operands, `H` and `I` whole lane tiles, no more
    tokens than the kernel wins at, and a cut that fits VMEM with x and y
    whole. Every rejection is counted
    (`pallas.gate_reject.grouped_expert_ffn.{reason}`) and the call takes
    the plain form."""
    from ...ops.pallas import gate_reject
    from ...ops.pallas.grouped_ffn import (GROUPED_FFN_MAX_TOKENS,
                                           grouped_ffn_tile)
    from .. import functional as F
    T, H = x.shape
    I = gate.shape[2]
    if not F._pallas_backend_ok():
        return gate_reject("grouped_expert_ffn", "backend")
    if x.dtype != jnp.bfloat16 or gate.dtype != jnp.bfloat16:
        return gate_reject("grouped_expert_ffn", "dtype")
    if H % 128 or I % 128:
        return gate_reject("grouped_expert_ffn", "shape")
    if T > GROUPED_FFN_MAX_TOKENS:
        return gate_reject("grouped_expert_ffn", "tokens")
    if not grouped_ffn_tile(T, H, I, block_rows(T), x.dtype.itemsize):
        return gate_reject("grouped_expert_ffn", "vmem")
    return True


class RoutedExperts(Layer):
    """See the module docstring. `forward(x)` takes [..., hidden] (Tensor
    or array) and returns the same kind; `routed(x, valid)` is the
    array-level call a served model uses: ([T, hidden] in x's dtype,
    [count] i32 pairs per held expert, [3] i32: the valid tokens' pairs
    on routed experts, on zero-compute experts, and the sum over tokens
    of a token's routed pairs squared)."""

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 held=None, routed_scaling_factor=1.0, shared_width=0,
                 dtype="float32", init_std=0.02, score_func="sigmoid",
                 norm_topk_prob=True, zero_experts=0):
        super().__init__(dtype=dtype)
        first, count = (0, num_experts) if held is None else held
        if not (0 <= first and count >= 1
                and first + count <= num_experts and top_k <= num_experts):
            raise ValueError(f"held range {held} outside the router's "
                             f"{num_experts} experts")
        if score_func not in ("sigmoid", "softmax") or zero_experts < 0:
            raise ValueError(f"score_func {score_func!r}, zero_experts "
                             f"{zero_experts}")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.zero_experts = int(zero_experts)
        self.score_func, self.norm_topk_prob = score_func, bool(norm_topk_prob)
        self.first, self.count = int(first), int(count)
        self.scaling = float(routed_scaling_factor)
        normal = I.Normal(0.0, init_std)

        def param(*shape):
            return self.create_parameter(list(shape),
                                         default_initializer=normal)

        H, W = int(hidden_size), int(expert_width)
        width = self.num_experts + self.zero_experts   # the router's
        self.router_weight = param(H, width)
        # the selection bias (`e_score_correction_bias`): float32 always
        self.router_bias = self.create_parameter(
            [width], dtype="float32", is_bias=True)
        self.gate, self.up = param(count, H, W), param(count, H, W)
        self.down = param(count, W, H)
        self.shared_width = int(shared_width)
        if shared_width:
            self.shared_gate = param(H, shared_width)
            self.shared_up = param(H, shared_width)
            self.shared_down = param(shared_width, H)

    def route(self, x):
        """x [T, H] -> (expert ids [T, top_k] i32 over the router's
        width, routed experts first and zero-compute experts after them,
        weights [T, top_k] f32). Selection on score + bias; weights from
        the scores alone, normalised over the chosen or not, scaled."""
        logits = jnp.dot(
            x.astype(jnp.float32),
            self.router_weight._value.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits) if self.score_func == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(scores + self.router_bias._value, self.top_k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        weights = self.scaling * chosen
        if self.norm_topk_prob:
            weights = weights / jnp.sum(chosen, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), weights

    def routed(self, x, valid=None):
        if valid is None:
            valid = jnp.ones((x.shape[0],), bool)
        with jax.named_scope("router"):
            idx, weights = self.route(x)
        with jax.named_scope("experts"):
            y, counts = self._held_products(x, idx, weights, valid)
            zero = (idx >= self.num_experts) & valid[:, None]
            if self.zero_experts:
                with jax.named_scope("zero_experts"):
                    w_zero = jnp.sum(jnp.where(zero, weights, 0.0), axis=-1)
                    y = y + w_zero[:, None] * x.astype(jnp.float32)
        if self.shared_width:
            with jax.named_scope("ffn"):
                y = y + _swiglu(x, self.shared_gate._value,
                                self.shared_up._value,
                                self.shared_down._value)
        with jax.named_scope("router"):    # what the routing came to
            real = jnp.sum((idx < self.num_experts) & valid[:, None],
                           axis=-1, dtype=jnp.int32)           # a token
            pairs = jnp.stack([jnp.sum(real),
                               jnp.sum(zero, dtype=jnp.int32),
                               jnp.sum(real * real)])
        return y.astype(x.dtype), counts, pairs

    def _held_products(self, x, idx, weights, valid):
        """Σ over the held experts a token chose, by the form the gate
        picks -> (y [T, H] f32, pairs per held expert [count] i32)."""
        # a zero-compute id lies past every held range: `_routed_expert_
        # ffn` gives it no row and no block, like an absent expert
        operands = (x, idx, weights, valid, self.gate._value,
                    self.up._value, self.down._value, self.first)
        if _grouped_kernel_eligible(x, self.gate._value):
            from ...core import monitor
            from ...ops.pallas import run_guarded
            from ...ops.pallas.grouped_ffn import grouped_ffn_cut
            # the cut this program compiles with, on the kernel's span and
            # as gauges per token count (t128 is a decode step of 128 slots)
            cut = grouped_ffn_cut(
                x.shape[0], self.top_k, self.count, x.shape[1],
                self.gate._value.shape[2], block_rows(x.shape[0]),
                x.dtype.itemsize)
            monitor.stat_set_many({
                f"pallas.grouped_expert_ffn.{name}.t{x.shape[0]}": value
                for name, value in cut.items()})
            return run_guarded(
                "grouped_expert_ffn",
                lambda: _grouped_expert_ffn(*operands), **cut)
        return _routed_expert_ffn(*operands)

    def forward(self, x):
        from ...core.tensor import Tensor
        v = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        y = self.routed(v.reshape(-1, v.shape[-1]))[0].reshape(v.shape)
        return Tensor(y, _internal=True) if isinstance(x, Tensor) else y
