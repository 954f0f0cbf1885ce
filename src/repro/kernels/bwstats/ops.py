"""Jit'd wrapper for the bwstats kernel: padding + dispatch + dict output.

``bwstats`` takes the raw ``TransferMonitor.history_matrix`` output
(arbitrary N, W) and returns the six statistics trimmed to N, as either
the Pallas kernel (default; interpret mode resolved from the platform)
or the jnp reference. ``publish_fleet_stats``
maps the result back onto GRIS attribute names — the fleet-scale version
of ``TransferMonitor.summary_attrs``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import bwstats_pallas
from .ref import bwstats_ref

__all__ = ["bwstats", "publish_fleet_stats"]

STAT_NAMES = ("min", "max", "mean", "std", "last", "ewma")


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("alpha", "block_n", "use_kernel", "interpret"))
def _dispatch(hist_t, counts, *, alpha, block_n, use_kernel, interpret):
    if use_kernel:
        return bwstats_pallas(
            hist_t, counts, alpha=alpha, block_n=block_n, interpret=interpret
        )
    return bwstats_ref(hist_t.T, counts, alpha=alpha)


def bwstats(
    hist: np.ndarray,  # [N, W] f32, left-aligned histories
    counts: np.ndarray,  # [N] i32
    *,
    alpha: float = 0.25,
    block_n: int = 256,
    use_kernel: bool = True,
    interpret: Optional[bool] = None,
) -> Dict[str, np.ndarray]:
    """→ {'min','max','mean','std','last','ewma'} each [N] f32."""
    n, w = hist.shape
    if n == 0:
        return {k: np.zeros((0,), np.float32) for k in STAT_NAMES}
    n_pad = max(_round_up(n, block_n), block_n)
    w_pad = max(_round_up(w, 128), 128)
    hist_t = np.zeros((w_pad, n_pad), dtype=np.float32)  # window-major
    hist_t[:w, :n] = np.asarray(hist).T
    counts_p = np.zeros((n_pad,), dtype=np.int32)
    counts_p[:n] = counts
    outs = _dispatch(
        jnp.asarray(hist_t), jnp.asarray(counts_p),
        alpha=alpha, block_n=block_n, use_kernel=use_kernel, interpret=interpret,
    )
    return {k: np.asarray(v)[:n] for k, v in zip(STAT_NAMES, outs)}


def publish_fleet_stats(
    hist: np.ndarray, counts: np.ndarray, peers: list, direction: str = "RD", **kw
) -> Dict[str, Dict[str, float]]:
    """Fleet-scale GRIS publication: per-peer attribute dicts mirroring
    ``TransferMonitor.source_attrs`` (the Figure-5 object class)."""
    stats = bwstats(hist, counts, **kw)
    out: Dict[str, Dict[str, float]] = {}
    for i, peer in enumerate(peers):
        out[peer] = {
            f"last{direction}Bandwidth": float(stats["last"][i]),
            f"Avg{direction}BandwidthToSource": float(stats["mean"][i]),
            f"Ewma{direction}BandwidthToSource": float(stats["ewma"][i]),
            f"Max{direction}Bandwidth": float(stats["max"][i]),
            f"Min{direction}Bandwidth": float(stats["min"][i]),
            f"Std{direction}Bandwidth": float(stats["std"][i]),
            "nSamplesToSource": float(counts[i]),
        }
    return out
