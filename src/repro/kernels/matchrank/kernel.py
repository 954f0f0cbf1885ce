"""Pallas TPU kernel: fused matchmaking (mask + rank + running top-k).

TPU adaptation of the Match Phase hot loop. Design notes:

  * The grid is ``(B // REQ_BLOCK, S // block_s)``: each step scores
    ``REQ_BLOCK`` (= 8, one f32 sublane tile) requests against one
    ``(block_s, A_PAD)`` candidate tile resident in VMEM, so the shared
    attribute table streams once per 8 requests. A_PAD is lane-aligned
    (128); block_s is a multiple of 128.
  * Results are laid out candidate-major on lanes: every per-request row
    (admit, mask, score) is a lane-dense ``[8, block_s]`` tile.
  * Per-term attribute *gathers* are a one-hot matmul
    ``sel · attrsᵀ`` on the MXU (``[8·T_PAD, A_PAD] × [A_PAD, block_s]``)
    at full f32 precision, so selected values are exact; a lane gather
    would serialize on the VPU. The per-term plan rides in columns
    (``[8·T_PAD, 1]``) that broadcast along the candidate lanes.
  * All six comparison ops are evaluated vectorized and the per-term op
    is chosen by boolean masks — branch-free VPU code.
  * The per-request top-k is carried across S-blocks in the resident
    top-k output tile ``[8, K_PAD]``; each of the k rounds is a masked
    ``max`` (best score) then a masked ``min`` (lowest global row among
    the ties), so the kernel needs no dynamic indexing and keeps
    ``lax.top_k``'s tie-break exactly. One pass over HBM per 8 requests.

Interpret mode is chosen by :func:`repro.kernels.resolve_interpret`: the
kernels compile through Mosaic on a TPU and run in the Pallas interpreter
on the CPU backend, where they are swept against :mod:`.ref` (see
tests/test_kernel_matchrank.py); tests/test_tpu_compile.py compiles them
for a TPU v5e ahead of time.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = float("-inf")

#: requests per grid step (one f32 sublane tile)
REQ_BLOCK = 8
#: lane width of the top-k carry/output tile (bounds k)
K_PAD = 128
#: index held by carry slots no candidate has filled yet (above every row)
_NO_ROW = 2**31 - 1

__all__ = ["REQ_BLOCK", "K_PAD", "matchrank_pallas", "matchrank_batched_pallas"]


def _dot_t(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``x · yᵀ`` ([M, A] × [N, A] → [M, N]) at full f32 precision."""
    return jax.lax.dot_general(
        x,
        y,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _matchrank_batched_kernel(
    # inputs (VMEM tiles)
    attrs_ref,  # [BLOCK_S, A_PAD] f32 (shared across the batch)
    valid_ref,  # [BLOCK_S, A_PAD] f32
    admit_ref,  # [R, BLOCK_S] f32 — the block's pre-masks
    sel_ref,  # [R·T_PAD, A_PAD] f32 — request-major one-hot term rows
    ops_ref,  # [R·T_PAD, 1] i32
    th_ref,  # [R·T_PAD, 1] f32
    act_ref,  # [R·T_PAD, 1] f32
    w_ref,  # [R, A_PAD] f32
    bias_ref,  # [R, 1] f32
    # outputs
    mask_ref,  # [R, BLOCK_S] f32
    score_ref,  # [R, BLOCK_S] f32
    topk_s_ref,  # [R, K_PAD] f32 — resident across S-blocks (the carry)
    topk_i_ref,  # [R, K_PAD] i32
    *,
    block_s: int,
    t_pad: int,
    k: int,
):
    si = pl.program_id(1)  # S-block index (innermost: sequential per block)
    r = REQ_BLOCK

    attrs = attrs_ref[...]
    validf = valid_ref[...]

    # ---- per-term values: one-hot matmul on the MXU, candidates on lanes ----
    sel = sel_ref[...]
    vals = _dot_t(sel, attrs)  # [R·T, S]
    vok = _dot_t(sel, validf) > 0.5

    th = th_ref[...]
    opc = jnp.broadcast_to(ops_ref[...], vals.shape)
    cmp = (
        ((opc == 0) & (vals < th))
        | ((opc == 1) & (vals <= th))
        | ((opc == 2) & (vals > th))
        | ((opc == 3) & (vals >= th))
        | ((opc == 4) & (vals == th))
        | ((opc == 5) & (vals != th))
    )
    act = jnp.broadcast_to(act_ref[...], vals.shape) > 0.5
    term_ok = jnp.logical_not(act) | (cmp & vok)
    # a request matches when all of its T_PAD term rows pass
    passed = term_ok.astype(jnp.float32).reshape(r, t_pad, block_s)
    mask = jnp.logical_and(jnp.min(passed, axis=1) > 0.5, admit_ref[...] > 0.5)

    # ---- linear rank with validity gating ----
    w = w_ref[...]
    score_raw = _dot_t(w, attrs) + bias_ref[...]  # [R, S]
    wactive = (jnp.abs(w) > 0).astype(jnp.float32)
    n_bad = _dot_t(wactive, 1.0 - validf)  # invalid weighted attrs per row
    rank = jnp.where(n_bad > 0, 0.0, score_raw)

    score = jnp.where(mask, rank, NEG_INF)
    mask_ref[...] = mask.astype(jnp.float32)
    score_ref[...] = score

    # ---- fused per-request top-k carry across S-blocks ----
    # The carry holds each request's best k (score, global row) so far.
    # Each round takes the best remaining score over [carry ++ block] and,
    # among equal scores, the lowest global row — lax.top_k's tie-break
    # (-inf included), without dynamic indexing. Rows are unique, so the
    # winner is knocked out of both sides by its row alone.
    @pl.when(si == 0)
    def _init():
        topk_s_ref[...] = jnp.full((r, K_PAD), NEG_INF, jnp.float32)
        topk_i_ref[...] = jnp.full((r, K_PAD), _NO_ROW, jnp.int32)

    row = si * block_s + jax.lax.broadcasted_iota(jnp.int32, (r, block_s), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (r, K_PAD), 1)
    carry_s = topk_s_ref[...]
    carry_i = topk_i_ref[...]
    carry_live = slot < k
    block_live = jnp.ones((r, block_s), dtype=jnp.bool_)
    new_s = jnp.full((r, K_PAD), NEG_INF, jnp.float32)
    new_i = jnp.full((r, K_PAD), _NO_ROW, jnp.int32)
    for j in range(k):
        best = jnp.maximum(
            jnp.max(jnp.where(carry_live, carry_s, NEG_INF), axis=1, keepdims=True),
            jnp.max(jnp.where(block_live, score, NEG_INF), axis=1, keepdims=True),
        )
        take_c = jnp.logical_and(carry_live, carry_s == best)
        take_b = jnp.logical_and(block_live, score == best)
        pick = jnp.minimum(
            jnp.min(jnp.where(take_c, carry_i, _NO_ROW), axis=1, keepdims=True),
            jnp.min(jnp.where(take_b, row, _NO_ROW), axis=1, keepdims=True),
        )
        new_s = jnp.where(slot == j, best, new_s)
        new_i = jnp.where(slot == j, pick, new_i)
        carry_live = jnp.logical_and(carry_live, carry_i != pick)
        block_live = jnp.logical_and(block_live, row != pick)
    topk_s_ref[...] = new_s
    topk_i_ref[...] = new_i


def matchrank_batched_pallas(
    attrs: jnp.ndarray,  # [S, A_PAD] f32 (S % block_s == 0, A_PAD % 128 == 0)
    valid: jnp.ndarray,  # [S, A_PAD] f32
    admit: jnp.ndarray,  # [B, S] f32 — per-request pre-mask
    sel: jnp.ndarray,  # [B, T_PAD, A_PAD] f32
    op_codes: jnp.ndarray,  # [B, T_PAD] i32
    thresholds: jnp.ndarray,  # [B, T_PAD] f32
    term_active: jnp.ndarray,  # [B, T_PAD] f32
    weights: jnp.ndarray,  # [B, A_PAD] f32
    bias: jnp.ndarray,  # [B] f32
    *,
    block_s: int = 512,
    k: int = 1,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-request fused match+rank+top-k over ONE candidate block.

    The request axis is padded to a multiple of ``REQ_BLOCK`` with
    requests that admit nothing; the grid is ``(B_pad // 8, S // block_s)``
    with the candidate axis innermost, so each block of 8 requests keeps
    its plan tiles resident while the shared ``attrs``/``valid`` tiles
    stream past once. ``interpret=None`` resolves from the platform
    (:func:`repro.kernels.resolve_interpret`).

    Returns (mask [B,S] bool, score [B,S] f32, topk_scores [B,k] f32,
    topk_idx [B,k] i32); top-k slots past a request's match count hold
    -inf with the lowest unmatched rows, as ``lax.top_k`` gives them.
    """
    s, a_pad = attrs.shape
    b, t_pad, a_pad2 = sel.shape
    assert a_pad == a_pad2, (a_pad, a_pad2)
    assert s % block_s == 0 and block_s % 128 == 0, (s, block_s)
    assert admit.shape == (b, s), (admit.shape, b, s)
    assert t_pad % 8 == 0, t_pad
    assert 1 <= k <= min(K_PAD, block_s), (k, block_s)
    r = REQ_BLOCK
    b_pad = -(-b // r) * r

    def rows(x):  # pad the request axis with zeros (requests admitting nothing)
        return jnp.pad(x, [(0, b_pad - b)] + [(0, 0)] * (x.ndim - 1))

    # the per-term plan as [B_pad·T_PAD, ·] rows: request-major term rows
    sel_rows = rows(sel.astype(jnp.float32)).reshape(b_pad * t_pad, a_pad)
    ops_col = rows(op_codes.astype(jnp.int32)).reshape(b_pad * t_pad, 1)
    th_col = rows(thresholds.astype(jnp.float32)).reshape(b_pad * t_pad, 1)
    act_col = rows(term_active.astype(jnp.float32)).reshape(b_pad * t_pad, 1)
    w_rows = rows(weights.astype(jnp.float32))
    bias_col = rows(bias.astype(jnp.float32)).reshape(b_pad, 1)
    admit_rows = rows(admit.astype(jnp.float32))

    kernel = functools.partial(
        _matchrank_batched_kernel, block_s=block_s, t_pad=t_pad, k=k
    )
    rt = r * t_pad
    in_specs = [
        pl.BlockSpec((block_s, a_pad), lambda bi, si: (si, 0)),  # attrs (shared)
        pl.BlockSpec((block_s, a_pad), lambda bi, si: (si, 0)),  # valid (shared)
        pl.BlockSpec((r, block_s), lambda bi, si: (bi, si)),  # admit
        pl.BlockSpec((rt, a_pad), lambda bi, si: (bi, 0)),  # sel
        pl.BlockSpec((rt, 1), lambda bi, si: (bi, 0)),  # ops
        pl.BlockSpec((rt, 1), lambda bi, si: (bi, 0)),  # thresholds
        pl.BlockSpec((rt, 1), lambda bi, si: (bi, 0)),  # active
        pl.BlockSpec((r, a_pad), lambda bi, si: (bi, 0)),  # weights
        pl.BlockSpec((r, 1), lambda bi, si: (bi, 0)),  # bias
    ]
    out_specs = (
        pl.BlockSpec((r, block_s), lambda bi, si: (bi, si)),  # mask
        pl.BlockSpec((r, block_s), lambda bi, si: (bi, si)),  # score
        pl.BlockSpec((r, K_PAD), lambda bi, si: (bi, 0)),  # top-k scores
        pl.BlockSpec((r, K_PAD), lambda bi, si: (bi, 0)),  # top-k rows
    )
    out_shapes = (
        jax.ShapeDtypeStruct((b_pad, s), jnp.float32),
        jax.ShapeDtypeStruct((b_pad, s), jnp.float32),
        jax.ShapeDtypeStruct((b_pad, K_PAD), jnp.float32),
        jax.ShapeDtypeStruct((b_pad, K_PAD), jnp.int32),
    )
    mask, score, topk_s, topk_i = pl.pallas_call(
        kernel,
        grid=(b_pad // r, s // block_s),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=resolve_interpret(interpret),
    )(
        attrs, valid, admit_rows, sel_rows, ops_col, th_col, act_col, w_rows,
        bias_col,
    )
    return mask[:b] > 0.5, score[:b], topk_s[:b, :k], topk_i[:b, :k]


def matchrank_pallas(
    attrs: jnp.ndarray,  # [S, A_PAD] f32 (S % block_s == 0, A_PAD % 128 == 0)
    valid: jnp.ndarray,  # [S, A_PAD] f32
    admit: jnp.ndarray,  # [S] f32
    sel: jnp.ndarray,  # [T_PAD, A_PAD] f32
    op_codes: jnp.ndarray,  # [T_PAD] i32
    thresholds: jnp.ndarray,  # [T_PAD] f32
    term_active: jnp.ndarray,  # [T_PAD] f32
    weights: jnp.ndarray,  # [A_PAD] f32
    bias: jnp.ndarray,  # [1] f32
    *,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One request through the batched kernel (a batch of one). Returns
    (mask [S] bool, score [S] f32, best_score [1] f32, best_idx [1] i32);
    with no match, best is (-inf, 0). Inputs must be pre-padded (ops.py
    does it)."""
    mask, score, best_s, best_i = matchrank_batched_pallas(
        attrs, valid, admit[None], sel[None], op_codes[None], thresholds[None],
        term_active[None], weights[None], jnp.reshape(bias, (1,)),
        block_s=block_s, k=1, interpret=interpret,
    )
    return mask[0], score[0], best_s[0], best_i[0]
