"""Dropless top-k dispatch of tokens to the sparse experts HELD HERE.

Expert parallelism gives each chip a range of a layer's experts. The layer
routes every token over ALL `E` experts (router product, scores and top-k
in f32: a softmax, or a sigmoid with a selection bias that chooses and does
not weigh; the weights renormalised over the `top_k` chosen when asked), keeps
the (token, expert) pairs whose expert is one of the `held` ones, computes
those experts' part of the result and adds it up by token. What the absent
experts would add is another chip's part; on one chip the layer runs
without its exchange and nothing stands in for the absent chips.

The engine's idiom (`unique_budget`, `dedup_overflow`): a STATIC budget of
held pairs and a counter of what went over it. Inside the budget no pair is
ever dropped, however uneven the routing (every token to one expert
included); a pair beyond it is left out AND counted, so that a caller can
treat the step as failed.

The step's cost is the BUDGET's, never the router's: every row block of the
budget is multiplied whether it holds pairs or not (an unused row is zero
and so is its product), as the engine's row operations run at their
`unique_budget`. A step's time then repeats from seed to seed whatever the
router learns to do.

Data flow, scatter-free up to the combine:
  1. a prefix sum and a `searchsorted` collect the held pairs at the
     budget's static length, in token order (the compaction
     `ops/compact.py::rank_compact` does for the engine's rows, written out
     here so that its device time is the expert layer's and not the
     engine's in a reduction by source file);
  2. a stable sort of those by expert groups them;
  3. each expert's group is laid out in whole row blocks of `block` rows (at
     least one a held expert, so the weight gradient of an expert without
     tokens is written as zero), `ceil(budget / block) + held` blocks in
     all; a block's expert and a row's source are found by prefix sums and
     `searchsorted`;
  4. `grouped_matmul` multiplies each row block by ITS expert's matrix: on a
     TPU a Pallas kernel whose weight block is indexed by a scalar-prefetched
     block -> expert map (consecutive blocks of one expert reuse the tile in
     VMEM, cast to the operands' dtype once an expert), with its own
     backward (the same kernel on the transposed weights for the rows'
     gradient, a per-expert accumulation of `x^T dy` for the weights');
     elsewhere a gathered-weights `einsum`;
  5. the rows are weighted and added into their tokens (one scatter-add).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeprec_tpu.utils import backend, scopes

HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


# -------------------------------------------------------------------- route


def route_topk(x, w_router, top_k: int, renormalise: bool = True, *,
               scoring: str = "softmax", bias=None, scale: float = 1.0,
               renorm_eps: float = 1e-20):
    """(weights [T, top_k] f32, experts [T, top_k] int32), router product
    and scores in f32. `scoring="softmax"`: a softmax over all the router's
    outputs, the `top_k` largest, renormalised over the chosen when asked.
    `scoring="sigmoid"`: a sigmoid an output; the `top_k` largest of
    `score + bias` are CHOSEN (`bias` [E], a selection bias that receives no
    gradient: it decides who is chosen and never what a chosen one
    weighs), each WEIGHS by its score without the bias, renormalised over
    the chosen when asked (`w / (sum of the chosen + renorm_eps)`), times
    `scale`."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        w, e = jax.lax.top_k(probs, top_k)
        if renormalise:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, e = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        w = jnp.take_along_axis(scores, e, axis=-1)
        if renormalise:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + renorm_eps)
        w = w * scale
    else:
        raise ValueError(f"scoring is softmax or sigmoid; got {scoring!r}")
    return w, e.astype(jnp.int32)


class Dispatch(NamedTuple):
    """The held pairs laid out in row blocks, one expert a block."""
    row_token: jnp.ndarray     # [R] int32 token of a row (0 where unused)
    row_pair: jnp.ndarray      # [R] int32 flat (token, k) index (0 unused)
    row_valid: jnp.ndarray     # [R] bool
    block_expert: jnp.ndarray  # [nb] int32 local expert of a block
    pairs: jnp.ndarray         # [] int32 held pairs, over the budget or not
    overflow: jnp.ndarray      # [] int32 pairs over the budget (left out)
    max_load: jnp.ndarray      # [] int32 the fullest held expert's rows


def num_blocks(pair_budget: int, held_count: int, block: int) -> int:
    return -(-pair_budget // block) + held_count


def dispatch_held(experts, held: Tuple[int, int], pair_budget: int,
                  block: int) -> Dispatch:
    """experts [T, K] int32 (each token's chosen experts, of all E);
    `held = (first, count)` the range of experts this chip holds."""
    first, count = held
    K = experts.shape[1]
    local = experts.reshape(-1) - first
    is_held = (local >= 0) & (local < count)
    rank = jnp.cumsum(is_held.astype(jnp.int32))
    n = rank[-1]
    j = jnp.arange(1, pair_budget + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(rank, j, side="left").astype(jnp.int32)
    loc = jnp.where(j <= n, local[jnp.minimum(idx, local.shape[0] - 1)],
                    count)                               # token order
    order = jnp.argsort(loc, stable=True)                # by expert
    pair, loc = idx[order], loc[order]
    sizes = jnp.sum(loc[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)                     # [count]
    nblk = jnp.maximum(-(-sizes // block), 1)
    blk_end = jnp.cumsum(nblk)
    grp_start = jnp.cumsum(sizes) - sizes
    nb = num_blocks(pair_budget, count, block)
    b = jnp.arange(nb, dtype=jnp.int32)
    block_expert = jnp.minimum(
        jnp.searchsorted(blk_end, b, side="right").astype(jnp.int32),
        count - 1)
    r = jnp.arange(nb * block, dtype=jnp.int32)
    e_r = block_expert[r // block]
    off = r - (blk_end - nblk)[e_r] * block
    valid = (r // block < blk_end[-1]) & (off < sizes[e_r])
    src = jnp.where(valid, pair[jnp.clip(grp_start[e_r] + off, 0,
                                         pair_budget - 1)], 0)
    return Dispatch(
        row_token=src // K, row_pair=src, row_valid=valid,
        block_expert=block_expert, pairs=n,
        overflow=jnp.maximum(n - pair_budget, 0), max_load=jnp.max(sizes))


# ------------------------------------------------------ the grouped products


def _expert_changes(be_ref, b):
    return jnp.logical_or(b == 0, be_ref[b] != be_ref[jnp.maximum(b - 1, 0)])


def _gmm_kernel(be_ref, x_ref, w_ref, o_ref, wc_ref, *, transpose_w: bool):
    from jax.experimental import pallas as pl

    # the weights are cast once an expert, not once a row block
    @pl.when(_expert_changes(be_ref, pl.program_id(0)))
    def _():
        wc_ref[...] = w_ref[0].astype(wc_ref.dtype)

    dims = (((1,), (1,)), ((), ())) if transpose_w \
        else (((1,), (0,)), ((), ()))
    o_ref[...] = jax.lax.dot_general(
        x_ref[...], wc_ref[...], dims, preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _gmm_pallas(x, w, block_expert, block: int, transpose_w: bool,
                interpret: bool):
    """x [R, K] @ w[expert of the row's block] -> [R, N] f32; with
    `transpose_w` w is [E, N, K] and is contracted over its last axis."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K = x.shape
    N = w.shape[1] if transpose_w else w.shape[2]
    name = (scopes.KERNEL_GROUPED_MATMUL_DX if transpose_w
            else scopes.KERNEL_GROUPED_MATMUL)
    with scopes.kernel_trace(name):
        return pl.pallas_call(
            functools.partial(_gmm_kernel, transpose_w=transpose_w),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R // block,),
                in_specs=[
                    pl.BlockSpec((block, K), lambda b, be: (b, 0)),
                    pl.BlockSpec((1,) + w.shape[1:],
                                 lambda b, be: (be[b], 0, 0)),
                ],
                out_specs=pl.BlockSpec((block, N), lambda b, be: (b, 0)),
                scratch_shapes=[pltpu.VMEM(w.shape[1:], x.dtype)],
            ),
            out_shape=jax.ShapeDtypeStruct((R, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name=name,
        )(block_expert, x, w)


def _tgmm_kernel(be_ref, x_ref, dy_ref, dw_ref):
    from jax.experimental import pallas as pl

    @pl.when(_expert_changes(be_ref, pl.program_id(0)))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dw_ref[0] += jax.lax.dot_general(
        x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _tgmm_pallas(x, dy, block_expert, experts: int, block: int,
                 interpret: bool):
    """dw[e] = sum over the row blocks of expert e of x_b^T dy_b:
    [E, K, N] f32. Every expert owns at least one block (dispatch_held), so
    every dw[e] is written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, K = x.shape
    N = dy.shape[1]
    with scopes.kernel_trace(scopes.KERNEL_GROUPED_MATMUL_DW):
        return pl.pallas_call(
            _tgmm_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(R // block,),
                in_specs=[
                    pl.BlockSpec((block, K), lambda b, be: (b, 0)),
                    pl.BlockSpec((block, N), lambda b, be: (b, 0)),
                ],
                out_specs=pl.BlockSpec((1, K, N), lambda b, be: (be[b], 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((experts, K, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name=scopes.KERNEL_GROUPED_MATMUL_DW,
        )(block_expert, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, block_expert, block, interpret):
    return _gmm_pallas(x, w, block_expert, block, False, interpret)


def _gmm_fwd(x, w, block_expert, block, interpret):
    return _gmm(x, w, block_expert, block, interpret), (x, w, block_expert)


def _gmm_bwd(block, interpret, res, dy):
    x, w, block_expert = res
    dyc = dy.astype(x.dtype)
    dx = _gmm_pallas(dyc, w, block_expert, block, True,
                     interpret).astype(x.dtype)
    dw = _tgmm_pallas(x, dyc, block_expert, w.shape[0], block,
                      interpret).astype(w.dtype)
    return dx, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x, w, block_expert, block: int, interpret: bool = False):
    """x [R, K] (row blocks of `block` rows, one expert a block) times
    w [E, K, N] -> [R, N] f32. Operands of the products are x's dtype (the
    weights are cast a tile at a time), accumulation f32. Every block is
    multiplied: a row no pair stands in is zero and comes back zero."""
    if backend.on_tpu() or interpret:
        return _gmm(x, w, block_expert, block,
                    interpret or not backend.on_tpu())
    nb = x.shape[0] // block
    y = jnp.einsum("bmk,bkn->bmn", x.reshape(nb, block, -1),
                   w.astype(x.dtype)[block_expert],
                   preferred_element_type=jnp.float32)
    return y.reshape(x.shape[0], -1)


# ---------------------------------------------------------------- the layer


def held_experts_apply(p, x, weights, experts, *, held: Tuple[int, int],
                       pair_budget: int, block: int = 128,
                       compute_dtype=jnp.bfloat16, interpret: bool = False,
                       activation=jax.nn.silu, count_live: bool = False):
    """The held experts' part of the expert layer,
    `w (activation(x Wg) * (x Wu)) Wd` a pair, or, where `p` has no `wg`
    (an expert without a gate), `w activation(x Wu) Wd`. p: `wg`, `wu`
    [held, d, f], `wd` [held, f, d]; x [T, d]; weights, experts [T, K] from
    `route_topk`. Returns (y [T, d] f32, {"pairs", "overflow",
    "max_load"}), and under `count_live` (for an activation that is 0 below
    0) "hidden_live" too: the hidden units of the rows pairs stand in that
    the gate's activation leaves above 0 (an unused row is zero and so is
    its gate)."""
    with scopes.scope(scopes.MOE_DISPATCH):
        d = Dispatch(*(checkpoint_name(a, scopes.KEPT_MOE_ROUTE) for a in
                       dispatch_held(experts, held, pair_budget, block)))
        xs = jnp.where(d.row_valid[:, None],
                       x.astype(compute_dtype)[d.row_token], 0)
        row_w = jnp.where(d.row_valid, weights.reshape(-1)[d.row_pair], 0.0)
    with scopes.scope(scopes.MOE_EXPERTS):
        mm = functools.partial(grouped_matmul, block_expert=d.block_expert,
                               block=block, interpret=interpret)
        # a pair's weight scales its hidden row, not its [d]-wide output:
        # the same product, a quarter of the elements
        if "wg" in p:
            gate = activation(mm(xs, p["wg"]))
            h = gate * mm(xs, p["wu"]) * row_w[:, None]
        else:
            gate = activation(mm(xs, p["wu"]))
            h = gate * row_w[:, None]
        ys = mm(h.astype(compute_dtype), p["wd"])
        counters = {"pairs": d.pairs, "overflow": d.overflow,
                    "max_load": d.max_load}
        if count_live:
            counters["hidden_live"] = jnp.sum(gate > 0, dtype=jnp.int32)
    with scopes.scope(scopes.MOE_DISPATCH):
        y = jnp.zeros(x.shape, jnp.float32).at[d.row_token].add(ys)
    return y, counters
