"""Two-stage hierarchical top-k over a sharded snapshot (DESIGN.md §9).

Stage 1 — per-shard fused matchrank+top-k: the existing batched kernel
(:func:`~repro.kernels.matchrank.kernel.matchrank_batched_pallas`) is
``vmap``-ed over the shard axis of a stacked ``[G, S_shard, A_PAD]``
candidate block, producing each request's k best candidates *per shard*
(``[G, B, k]``). On a multi-device mesh the stacked block can be laid out
with :func:`repro.parallel.sharding.shard_axis_mesh` /
``distribute_shards`` so the vmapped kernel partitions along the shard
axis; on one device it runs as a batched loop — same results either way.

Stage 2 — merge: per-shard candidate lists are globalized (local index +
shard row offset), flattened **shard-major** into ``[B, G·k]`` and merged
into the global top-k by a small Pallas kernel (k knockout rounds of
masked max/min reductions, 8 requests per grid step).

Tie-break contract (property-tested): every per-shard list is
rank-descending with ties at the lowest local index, and the shard-major
flattening makes candidate *position* order agree with *global row*
order within any equal-score run — so first-maximum knockout in the
merge reproduces exactly the ``lax.top_k`` tie-break (lowest global row
index) of an equivalent flat snapshot.

:func:`sharded_sparse_topk` is the CPU steady-state twin: the rank-order
sparse walk (:mod:`.sparse`) runs per shard against per-shard cached
rank orders, then the same merge (NumPy reference) combines candidates.
"""

from __future__ import annotations

import functools
import math
from contextlib import nullcontext
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

from .kernel import NEG_INF, REQ_BLOCK, matchrank_batched_pallas
from .ops import BatchedPlan, KernelPlan, stack_plans
from .ref import matchrank_batched_ref, merge_topk_ref
from .sparse import IntervalBatch, topk_in_rank_order

__all__ = [
    "MERGE_K_PAD",
    "merge_topk_pallas",
    "sharded_matchrank_topk",
    "sharded_sparse_topk",
]

#: lane-aligned output width of the merge kernel (bounds k)
MERGE_K_PAD = 128


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _merge_topk_kernel(
    scores_ref,  # [R, C_PAD] f32 — the block's flattened per-shard candidates
    idx_ref,  # [R, C_PAD] i32 — matching global row indices
    out_s_ref,  # [R, MERGE_K_PAD] f32
    out_i_ref,  # [R, MERGE_K_PAD] i32
    *,
    k: int,
):
    s = scores_ref[...]
    idx = idx_ref[...]
    r, c_pad = s.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (r, c_pad), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (r, MERGE_K_PAD), 1)
    out_s = jnp.full((r, MERGE_K_PAD), NEG_INF, dtype=jnp.float32)
    out_i = jnp.zeros((r, MERGE_K_PAD), dtype=jnp.int32)
    # k knockout rounds of masked reductions: the best score, then the
    # lowest position holding it (position order == global-row order
    # within ties, by the shard-major layout), then that position's row
    for j in range(k):
        best = jnp.max(s, axis=1, keepdims=True)
        m = jnp.min(jnp.where(s == best, pos, c_pad), axis=1, keepdims=True)
        at_m = pos == m
        row = jnp.max(jnp.where(at_m, idx, jnp.iinfo(jnp.int32).min), axis=1, keepdims=True)
        out_s = jnp.where(slot == j, best, out_s)
        out_i = jnp.where(slot == j, row, out_i)
        s = jnp.where(at_m, NEG_INF, s)
    out_s_ref[...] = out_s
    out_i_ref[...] = out_i


def merge_topk_pallas(
    cand_scores: jnp.ndarray,  # [B, C] f32
    cand_idx: jnp.ndarray,  # [B, C] i32
    k: int,
    *,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merge per-shard candidate lists into the global top-k.

    Pads the candidate axis to the lane width with (-inf, 0) and the
    request axis to ``REQ_BLOCK`` rows, one block of 8 requests per grid
    step, and returns (scores [B, k] f32, idx [B, k] i32); slots past a
    request's match count hold -inf (index meaningless there, as in the
    fused kernel). ``interpret=None`` resolves from the platform
    (:func:`repro.kernels.resolve_interpret`).
    """
    assert 1 <= k <= MERGE_K_PAD, (k, MERGE_K_PAD)
    b, c = cand_scores.shape
    b_pad = _round_up(b, REQ_BLOCK)
    c_pad = max(_round_up(c, 128), 128)
    scores = jnp.full((b_pad, c_pad), NEG_INF, dtype=jnp.float32)
    scores = scores.at[:b, :c].set(cand_scores.astype(jnp.float32))
    idx = jnp.zeros((b_pad, c_pad), dtype=jnp.int32)
    idx = idx.at[:b, :c].set(cand_idx.astype(jnp.int32))

    kernel = functools.partial(_merge_topk_kernel, k=k)
    out_shapes = (
        jax.ShapeDtypeStruct((b_pad, MERGE_K_PAD), jnp.float32),
        jax.ShapeDtypeStruct((b_pad, MERGE_K_PAD), jnp.int32),
    )
    in_specs = [
        pl.BlockSpec((REQ_BLOCK, c_pad), lambda bi: (bi, 0)),  # scores
        pl.BlockSpec((REQ_BLOCK, c_pad), lambda bi: (bi, 0)),  # idx
    ]
    out_specs = (
        pl.BlockSpec((REQ_BLOCK, MERGE_K_PAD), lambda bi: (bi, 0)),
        pl.BlockSpec((REQ_BLOCK, MERGE_K_PAD), lambda bi: (bi, 0)),
    )
    out_s, out_i = pl.pallas_call(
        kernel,
        grid=(b_pad // REQ_BLOCK,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=resolve_interpret(interpret),
    )(scores, idx)
    return out_s[:b, :k], out_i[:b, :k]


@functools.partial(
    jax.jit, static_argnames=("k", "block_s", "use_kernel", "interpret")
)
def _stage1_sharded(
    attrs, valid, admit, sel, op_codes, thresholds, term_role, weights, bias,
    offsets,
    *, k: int, block_s: int, use_kernel: bool, interpret: Optional[bool],
):
    """Per-shard fused matchrank+top-k, vmapped over the shard axis.
    → (cand_scores [B, G·k] f32, cand_idx [B, G·k] i32) in shard-major
    candidate order, indices globalized by the shard row offsets."""

    def one(a, v, ad):
        if use_kernel:
            _, _, tks, tki = matchrank_batched_pallas(
                a, v, ad, sel, op_codes, thresholds, term_role, weights,
                bias, block_s=block_s, k=k, interpret=interpret,
            )
        else:
            _, _, tks, tki = matchrank_batched_ref(
                a, v, ad, sel, op_codes, thresholds, term_role, weights,
                bias, k=k,
            )
        return tks, tki

    tks, tki = jax.vmap(one)(attrs, valid, admit)  # [G, B, k]
    gidx = tki.astype(jnp.int32) + offsets[:, None, None].astype(jnp.int32)
    b = tks.shape[1]
    cand_s = jnp.transpose(tks, (1, 0, 2)).reshape(b, -1)  # [B, G·k]
    cand_i = jnp.transpose(gidx, (1, 0, 2)).reshape(b, -1)
    return cand_s, cand_i


def _split_admit(
    admit: Optional[np.ndarray],
    b: int,
    counts: np.ndarray,
    offsets: np.ndarray,
    s_shard_pad: int,
) -> np.ndarray:
    """Global [B, n] pre-mask → stacked [G, B, S_shard] per-shard masks.
    Padded rows are always masked out (they carry no valid attributes but
    a requirement-free request would otherwise admit them)."""
    g = len(counts)
    out = np.zeros((g, b, s_shard_pad), dtype=np.float32)
    for gi in range(g):
        c = int(counts[gi])
        if c == 0:
            continue
        off = int(offsets[gi])
        if admit is None:
            out[gi, :, :c] = 1.0
        else:
            out[gi, :, :c] = np.asarray(admit, dtype=np.float32)[:, off : off + c]
    return out


def sharded_matchrank_topk(
    attrs: Any,  # [G, S_shard, A_PAD] f32 — stacked per-shard blocks
    valid: Any,  # [G, S_shard, A_PAD] f32
    plans: "BatchedPlan | Sequence[KernelPlan]",
    *,
    counts: np.ndarray,  # [G] live rows per shard
    offsets: np.ndarray,  # [G] global row offset per shard
    k: int = 1,
    admit: Optional[np.ndarray] = None,  # [B, n] global pre-mask
    block_s: int = 512,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
    merge_kernel: bool = True,
) -> Tuple[np.ndarray, np.ndarray]:
    """Device-parallel hierarchical top-k: per-shard fused kernel (vmap
    over shards) + merge kernel. → (topk_idx [B, k] i64 **global** rows,
    topk_scores [B, k] f32); empty slots hold (-1, -inf).

    Equal to flat ``lax.top_k`` over the dense scores, tie-break included
    (see module docstring). ``merge_kernel=False`` swaps stage 2 for the
    NumPy reference (parity tests).
    """
    batched = plans if isinstance(plans, BatchedPlan) else stack_plans(list(plans))
    s_shard_pad = int(attrs.shape[1])
    if s_shard_pad % block_s:
        # shard padding smaller/misaligned vs the requested S-block (e.g.
        # a snapshot built with a finer block_s): the largest common block
        # keeps the kernel's grid exact
        block_s = math.gcd(s_shard_pad, block_s) or s_shard_pad
    admit_g = _split_admit(admit, batched.b, counts, offsets, s_shard_pad)
    cand_s, cand_i = _stage1_sharded(
        attrs, valid, jnp.asarray(admit_g),
        jnp.asarray(batched.sel), jnp.asarray(batched.op_codes),
        jnp.asarray(batched.thresholds), jnp.asarray(batched.term_role),
        jnp.asarray(batched.weights), jnp.asarray(batched.bias),
        jnp.asarray(np.asarray(offsets, dtype=np.int32)),
        k=k, block_s=block_s, use_kernel=use_kernel, interpret=interpret,
    )
    if merge_kernel:
        ts, ti = merge_topk_pallas(cand_s, cand_i, k, interpret=interpret)
        ts, ti = np.asarray(ts), np.asarray(ti)
    else:
        ts, ti = merge_topk_ref(np.asarray(cand_s), np.asarray(cand_i), k)
    ti = np.where(np.isneginf(ts), -1, ti.astype(np.int64))
    return ti, ts.astype(np.float32)


def sharded_sparse_topk(
    shards: Sequence[Tuple[np.ndarray, np.ndarray]],  # [(attrs, valid)] per shard
    batch: IntervalBatch,
    *,
    k: int = 1,
    offsets: Optional[np.ndarray] = None,
    admit: Optional[np.ndarray] = None,  # [B, n] global pre-mask
    rank_order: Optional[Callable[[int, np.ndarray, float], Tuple]] = None,
    observe: Optional[Callable[[int], Any]] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """CPU steady-state twin of :func:`sharded_matchrank_topk`: rank-order
    sparse walk per shard, then the reference merge.

    ``rank_order(g, weights, bias) → (order, svals)`` supplies each
    shard's cached rank order (``ShardedSnapshot.shard_rank_order``);
    ``observe(g)`` may return a context manager wrapping shard g's walk
    (the broker passes tracer spans feeding its per-shard latency
    histogram). → (topk_idx [B, k] i64 global rows, topk_scores [B, k]);
    empty slots hold (-1, -inf).
    """
    parts_i: List[np.ndarray] = []
    parts_s: List[np.ndarray] = []
    pos = 0
    for g, (attrs, valid) in enumerate(shards):
        c = attrs.shape[0]
        off = int(offsets[g]) if offsets is not None else pos
        pos += c
        adm = None
        if admit is not None:
            adm = np.asarray(admit)[:, off : off + c]
        ro = None
        if rank_order is not None:
            ro = functools.partial(rank_order, g)
        cm = observe(g) if observe is not None else nullcontext()
        with cm:
            ti, ts = topk_in_rank_order(
                attrs, valid, batch, k=k, admit=adm, rank_order=ro
            )
        parts_i.append(np.where(ti >= 0, ti + off, ti))
        parts_s.append(ts)
    cand_i = np.concatenate(parts_i, axis=1)  # [B, G·k] shard-major
    cand_s = np.concatenate(parts_s, axis=1)
    ts, ti = merge_topk_ref(cand_s, cand_i, k)
    ti = np.where(np.isneginf(ts), -1, ti.astype(np.int64))
    return ti, ts
