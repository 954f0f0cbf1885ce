"""Pure-jnp oracle for the fused match+rank+top-1 kernel.

Semantics contract (shared with kernel.py and property-tested against the
ClassAd interpreter through ops.py):

  * ``terms``: rows of (one-hot column, opcode, threshold, role). Role 1
    is a requirement, ``2 + j`` a gate term of rank alternative j, 0
    padding. A term on an *invalid* attribute is Undefined ⇒ it fails
    (fail-closed, like the interpreter's symmetric match) — unless its
    opcode carries :data:`UNDEF_PASSES` (``isUndefined(a) || a OP c``),
    when it passes. The requirements are the conjunction of role-1 terms.
  * ``rank``: :data:`RANK_SLOTS` alternatives; the rank is the first whose
    gate terms all pass (no gate terms: always). Alternative j's value is
    ``(w[j]·x + b[j]) / (w[R+j]·x + b[R+j])``; if any attribute with a
    non-zero weight in either form is invalid, or the denominator is 0,
    the value is 0.0 (Condor's non-numeric-rank convention). A plain
    linear rank is alternative 0, ungated, over a denominator of 1.
  * ``admit``: a caller-supplied pre-mask (folded server policies).
  * score output: rank where matched, ``-inf`` where not (top-k ready).
  * best output: arg-top-1 (score, index), ties → lowest index.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

#: opcode encoding shared with core.compile.OPCODES
OP_LT, OP_LE, OP_GT, OP_GE, OP_EQ, OP_NE = 0, 1, 2, 3, 4, 5
#: opcode flag: an Undefined attribute passes the term
UNDEF_PASSES = 8
#: rank alternatives a plan carries (the default read ad's chain has 3)
RANK_SLOTS = 3

NEG_INF = float("-inf")


def rank_value(num, den, bad):
    """One alternative's value: ``num / den``, 0.0 where ``bad`` (an invalid
    weighted attribute) or the denominator is 0. A denominator of exactly
    1 leaves ``num`` as it is."""
    q = jnp.where(den == 1.0, num, num / jnp.where(den == 0.0, 1.0, den))
    return jnp.where(bad | (den == 0.0), 0.0, q)


def _term_pass(vals, vok, op_codes, thresholds):
    """Per-term pass: the comparison where the attribute is valid, the
    opcode's :data:`UNDEF_PASSES` flag where it is not."""
    undef = op_codes >= UNDEF_PASSES
    opc = jnp.where(undef, op_codes - UNDEF_PASSES, op_codes)
    th = thresholds
    r = jnp.where(opc == OP_LT, vals < th, False)
    r = jnp.where(opc == OP_LE, vals <= th, r)
    r = jnp.where(opc == OP_GT, vals > th, r)
    r = jnp.where(opc == OP_GE, vals >= th, r)
    r = jnp.where(opc == OP_EQ, vals == th, r)
    r = jnp.where(opc == OP_NE, vals != th, r)
    return jnp.where(vok, r, undef)


def matchrank_batched_ref(
    attrs: jnp.ndarray,  # [S, A] f32 — ONE shared candidate block
    valid: jnp.ndarray,  # [S, A] bool/f32
    admit: jnp.ndarray,  # [B, S] bool/f32 — per-request pre-mask
    sel: jnp.ndarray,  # [B, T, A] f32 one-hot rows
    op_codes: jnp.ndarray,  # [B, T] i32
    thresholds: jnp.ndarray,  # [B, T] f32
    term_role: jnp.ndarray,  # [B, T] f32
    weights: jnp.ndarray,  # [B, 2R, A] f32: numerators, then denominators
    bias: jnp.ndarray,  # [B, 2R] f32
    *,
    k: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-request oracle: B stacked plans against one candidate block.

    The candidate table is shared across the batch (the fleet scenario:
    one published GRIS snapshot, many concurrent selections). Returns
    (mask [B,S] bool, score [B,S] f32, topk_scores [B,k], topk_idx [B,k]);
    top-k slot j beyond the number of matches holds -inf. Ties → lowest
    candidate index (lax.top_k is index-stable).
    """
    attrs = attrs.astype(jnp.float32)
    validf = valid.astype(jnp.float32)
    weights, bias = weights.astype(jnp.float32), bias.astype(jnp.float32)

    # per-(request, term) values: [S,A] x [B,T,A] -> [B,S,T]
    vals = jnp.einsum("sa,bta->bst", attrs, sel.astype(jnp.float32))
    vok = jnp.einsum("sa,bta->bst", validf, sel.astype(jnp.float32)) > 0.5
    term_ok = _term_pass(
        vals, vok, op_codes[:, None, :], thresholds[:, None, :].astype(jnp.float32)
    )
    role = term_role[:, None, :]  # [B,1,T]

    def all_of(j):  # [B,S]: every term of role j passes (none: True)
        return jnp.all(jnp.where(role == j, term_ok, True), axis=-1)

    mask = all_of(1) & admit.astype(bool)  # [B,S]

    # the alternatives' numerators and denominators, with validity gating
    lin = jnp.einsum("sa,bqa->bqs", attrs, weights) + bias[:, :, None]  # [B,2R,S]
    wactive = (jnp.abs(weights) > 0).astype(jnp.float32)
    bad = jnp.einsum("sa,bqa->bqs", 1.0 - validf, wactive) > 0
    rank = jnp.zeros(mask.shape, jnp.float32)
    for j in reversed(range(RANK_SLOTS)):
        val = rank_value(lin[:, j], lin[:, RANK_SLOTS + j], bad[:, j] | bad[:, RANK_SLOTS + j])
        rank = jnp.where(all_of(2 + j), val, rank)

    score = jnp.where(mask, rank, NEG_INF)  # [B,S]
    k_eff = min(k, score.shape[-1])
    topk_scores, topk_idx = jax.lax.top_k(score, k_eff)
    return mask, score, topk_scores, topk_idx.astype(jnp.int32)


def matchrank_ref(
    attrs: jnp.ndarray,  # [S, A] f32
    valid: jnp.ndarray,  # [S, A] bool/f32
    sel: jnp.ndarray,  # [T, A] f32 one-hot rows (padding rows all-zero)
    op_codes: jnp.ndarray,  # [T] i32
    thresholds: jnp.ndarray,  # [T] f32
    term_role: jnp.ndarray,  # [T] f32 (padding terms 0)
    weights: jnp.ndarray,  # [2R, A] f32
    bias: jnp.ndarray,  # [2R] f32
    admit: jnp.ndarray,  # [S] bool/f32
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One request: the batched oracle for a batch of one. Returns (mask
    [S] bool, score [S] f32, best_score [1] f32, best_idx [1] i32)."""
    mask, score, best_s, best_i = matchrank_batched_ref(
        attrs, valid, admit[None], sel[None], op_codes[None], thresholds[None],
        term_role[None], weights[None], bias[None], k=1,
    )
    return mask[0], score[0], best_s[0], best_i[0]
