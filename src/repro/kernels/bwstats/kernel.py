"""Pallas TPU kernel: one-pass windowed bandwidth statistics.

The information plane of a 1000-node job tracks a per-(endpoint, client)
bandwidth history — N series of W observations. Publishing predictor
attributes (§3.2 / Figure 4-5 extensions) means reducing every series to
min/max/mean/std/last/EWMA after each batch of observations. That is a
single HBM pass: ``4·N·W`` bytes in, ``6·N·4`` bytes out — memory-bound,
so the kernel fuses all six statistics into one read of the history tile.

Layout: the window W is the sublane axis (padded to 128) and the series
axis N the lane axis, tiled by the grid (BLOCK_N a multiple of 128), so
every statistic is a lane-dense ``[1, BLOCK_N]`` row of one ``[8, N]``
output. The EWMA is evaluated as a dot with decay weights computed
in-register from the window index — a VPU expression, not a sequential
scan (state-space-style recurrences lowered to exponent arithmetic, the
same trick our SSD layer uses at model scale).

Interpret mode is chosen by :func:`repro.kernels.resolve_interpret`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BIG = 3.4e38
#: output rows: min, max, mean, std, last, ewma (+2 unused, one sublane tile)
_STAT_ROWS = 8


def _bwstats_kernel(
    hist_ref,  # [W_PAD, BLOCK_N] f32 — one series per lane
    counts_ref,  # [1, BLOCK_N] i32
    out_ref,  # [8, BLOCK_N] f32 — one statistic per row
    *,
    alpha: float,
):
    hist = hist_ref[...]
    cnt = counts_ref[...]  # [1, N] i32
    pos = jax.lax.broadcasted_iota(jnp.int32, hist.shape, 0)  # [W, N]
    m = pos < cnt
    cntf = jnp.maximum(cnt.astype(jnp.float32), 1.0)

    mn = jnp.min(jnp.where(m, hist, BIG), axis=0, keepdims=True)
    mx = jnp.max(jnp.where(m, hist, -BIG), axis=0, keepdims=True)
    s1 = jnp.sum(jnp.where(m, hist, 0.0), axis=0, keepdims=True)
    mean = s1 / cntf
    # two-pass variance: E[x²]−E[x]² cancels catastrophically in f32 for
    # bandwidth-scale values (~1e9); the tile is already in VMEM so the
    # second pass is free
    d = jnp.where(m, hist - mean, 0.0)
    var = jnp.sum(d * d, axis=0, keepdims=True) / cntf
    std = jnp.sqrt(var)
    last = jnp.sum(jnp.where(pos == cnt - 1, hist, 0.0), axis=0, keepdims=True)

    expo = jnp.maximum((cnt - 1 - pos).astype(jnp.float32), 0.0)
    decay = jnp.power(jnp.float32(1.0 - alpha), expo)  # exact at alpha=1
    wgt = jnp.where(pos == 0, decay, jnp.float32(alpha) * decay)
    ewma = jnp.sum(jnp.where(m, hist * wgt, 0.0), axis=0, keepdims=True)

    live = cnt > 0
    for row, stat in enumerate((mn, mx, mean, std, last, ewma)):
        out_ref[row : row + 1, :] = jnp.where(live, stat, 0.0)


def bwstats_pallas(
    hist_t: jnp.ndarray,  # [W_PAD, N] f32 (W_PAD % 128 == 0, N % block_n == 0)
    counts: jnp.ndarray,  # [N] i32
    *,
    alpha: float = 0.25,
    block_n: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, ...]:
    """Six [N] statistics (min, max, mean, std, last, EWMA) of N series
    held window-major (``hist_t[w, n]`` is observation w of series n).
    ``interpret=None`` resolves from the platform."""
    w_pad, n = hist_t.shape
    assert n % block_n == 0 and block_n % 128 == 0, (n, block_n)
    kernel = functools.partial(_bwstats_kernel, alpha=alpha)
    out = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((w_pad, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((_STAT_ROWS, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((_STAT_ROWS, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(hist_t, jnp.reshape(counts, (1, n)))
    return tuple(out[row] for row in range(6))
