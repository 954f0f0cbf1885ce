"""Divisibility-aware sharding policy: every leaf gets a PartitionSpec.

The policy maps (tree path, logical shape) → PartitionSpec for parameters,
optimizer state, activations and caches, on meshes with axes drawn from
``('pod', 'data', 'model')``:

  * vocab / d_ff / attention heads shard on ``model`` (tensor parallel),
  * batch shards on ``(pod, data)`` (data parallel),
  * optimizer master/moments additionally shard on ``data`` (ZeRO-1) and
    optionally parameters too (ZeRO-3 / FSDP) — the flag that makes
    nemotron-340b training fit,
  * MoE experts shard on ``model`` when ``E % model == 0`` and
    ``expert_parallel=True`` (the perf-hillclimb axis), else every
    expert's d_ff TP-shards,
  * decode KV caches shard heads on ``model``; for ``long_500k`` (batch 1)
    the cache *sequence* dimension shards on ``data`` — sequence-parallel
    attention over the cached context,
  * any dimension that does not divide its axis is replicated on it
    (never crash): whisper-base's 8 heads on a 16-way model axis replicate
    attention but still shard its d_ff=2048. This degradation is reported,
    not hidden — ``explain()`` returns every fallback the policy took.

Stacked-layer parameters (leading ``n_periods`` axis from scan-over-layers)
are detected by path (``slots``) and get a leading ``None``.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingPolicy",
    "tree_shardings",
    "tree_specs",
]


def _axis_size(mesh: Mesh, name: str) -> int:
    try:
        return mesh.shape[name]
    except (KeyError, TypeError):
        return 1


@dataclass
class ShardingPolicy:
    """Sharding rules for one (arch config, mesh) pair."""

    mesh: Mesh
    expert_parallel: bool = False  # EP vs per-expert TP for MoE weights
    zero3: bool = False  # shard params over 'data' as well (FSDP)
    zero1: bool = True  # shard optimizer state over 'data'
    seq_shard_cache: bool = False  # long_500k: KV-cache sequence over 'data'
    cache_kv_heads: Optional[int] = None  # arch's n_kv_heads (cache sharding)
    fallbacks: List[str] = field(default_factory=list)

    # ---- helpers ---------------------------------------------------------
    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.mesh.axis_names if a in ("pod", "data"))

    @property
    def model_size(self) -> int:
        return _axis_size(self.mesh, "model")

    @property
    def data_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= _axis_size(self.mesh, a)
        return n

    def _shard_if(self, dim: int, axis: str, what: str) -> Optional[str]:
        size = _axis_size(self.mesh, axis)
        if size <= 1:
            return None
        if dim % size == 0:
            return axis
        self.fallbacks.append(f"{what}: dim {dim} !% {axis}({size}) — replicated")
        return None

    def _data_axis_for(self, dim: int, what: str):
        """Largest data-axis combination that divides ``dim``."""
        axes = self.dp_axes
        if not axes:
            return None
        full = 1
        for a in axes:
            full *= _axis_size(self.mesh, a)
        if dim % full == 0:
            return axes if len(axes) > 1 else axes[0]
        # try just 'data'
        if "data" in axes and dim % _axis_size(self.mesh, "data") == 0:
            return "data"
        self.fallbacks.append(f"{what}: dim {dim} !% data axes — replicated")
        return None

    # ---- parameters ---------------------------------------------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """PartitionSpec for a parameter leaf. ``path`` is the '/'-joined
        tree path; ``shape`` the *full* array shape (incl. stacking)."""
        stacked = "/slots/" in path or path.startswith("slots/") or "/enc/" in path
        logical = shape[1:] if stacked and len(shape) > 1 else shape
        spec = self._param_spec_logical(path, logical)
        out = (None,) + tuple(spec) if stacked and len(shape) > 1 else tuple(spec)
        # ZeRO-3: additionally shard the largest replicated dim over data
        if self.zero3 and len(shape) >= 2:
            out = self._add_data_sharding(out, shape, path)
        return P(*out)

    def _param_spec_logical(self, path: str, s: Tuple[int, ...]) -> Tuple:
        name = path.rsplit("/", 1)[-1]
        m = "model"

        if name in ("embedding",):  # [V, D] — vocab on model
            return (self._shard_if(s[0], m, path), None)
        if name in ("head",):  # [D, V]
            return (None, self._shard_if(s[1], m, path))
        if name in ("pos_embed",):
            return (None, None)
        if name in ("wq", "wk", "wv"):  # [D, H*hd] — heads on model
            return (None, self._shard_if(s[1], m, path))
        if name in ("wo",) and "attn" in path or name == "wo" and "cross" in path:
            return (self._shard_if(s[0], m, path), None)
        if name in ("bq", "bk", "bv"):
            return (self._shard_if(s[0], m, path),)
        if name in ("wi", "wg") and "moe" in path:  # [E, D, F]
            if self.expert_parallel:
                e = self._shard_if(s[0], m, path + "(EP)")
                if e:
                    return (e, None, None)
            return (None, None, self._shard_if(s[2], m, path))
        if name == "wo" and "moe" in path:  # [E, F, D]
            if self.expert_parallel:
                e = self._shard_if(s[0], m, path + "(EP)")
                if e:
                    return (e, None, None)
            return (None, self._shard_if(s[1], m, path), None)
        if name in ("wi", "wg"):  # mlp [D, F]
            return (None, self._shard_if(s[1], m, path))
        if name == "wo":  # mlp [F, D]
            return (self._shard_if(s[0], m, path), None)
        if name == "router":  # [D, E] — replicated (tiny, avoids a2a on logits)
            return (None, None)
        if name in ("z_proj", "x_proj"):  # ssm [D, di] — heads on model
            return (None, self._shard_if(s[1], m, path))
        if name == "dt_proj":  # [D, nh] — aligned with head sharding
            return (None, self._shard_if(s[1], m, path))
        if name == "bc_proj":  # [D, 2·G·N] — tiny, group-broadcast: replicate
            return (None, None)
        if name == "out_proj":  # ssm [di, D]
            return (self._shard_if(s[0], m, path), None)
        if name in ("conv_x_w",):  # [K, di]
            return (None, self._shard_if(s[1], m, path))
        if name in ("conv_x_b",):
            return (self._shard_if(s[0], m, path),)
        if name in ("conv_bc_w", "conv_bc_b"):
            return tuple(None for _ in s)
        if name == "scale" and "ssm/norm" in path:  # gated norm over di
            return (self._shard_if(s[0], m, path),)
        # norms, scalars (A_log, D, dt_bias), biases: replicated
        return tuple(None for _ in s)

    def _add_data_sharding(self, spec: Tuple, shape: Tuple[int, ...], path: str) -> Tuple:
        """ZeRO-3: shard the largest not-yet-sharded dim over 'data'."""
        if "data" not in self.mesh.axis_names:
            return spec
        # a mesh axis may appear at most once per spec (zero3 params already
        # consumed 'data' ⇒ zero1 opt sharding is a no-op on top)
        for entry in spec:
            if entry == "data" or (isinstance(entry, tuple) and "data" in entry):
                return spec
        d = _axis_size(self.mesh, "data")
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if spec[i] is None and shape[i] % d == 0 and shape[i] >= d:
                out = list(spec)
                out[i] = "data"
                return tuple(out)
        if len(shape) >= 2:  # scalars/1-D replicating is expected, not a fallback
            self.fallbacks.append(f"{path}: zero3 found no dim % data({d})")
        return spec

    # ---- optimizer state -------------------------------------------------------
    def opt_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """Optimizer master/moments: param spec + ZeRO-1 data sharding.

        Blockwise-int8 moments (QTensor leaves ``.../q``, ``.../scale`` of
        shape [Nblk, B]) lose the param's dimensionality, so they shard
        their block dim over *all* axes that divide — for a 340B model the
        difference is 42 GB vs 2.7 GB of moments per device."""
        name = path.rsplit("/", 1)[-1]
        if name in ("q", "scale") and len(shape) in (2, 3):
            # QTensor leaves: spread every axis that divides across the
            # block dim first, then the lead dim (q [L, nblk, B] or
            # [nblk, B]) — the embedding's blocks-per-row may be small
            # (D/256) while its vocab lead dim shards fine.
            dims = [1, 0] if len(shape) == 3 else [0]
            spec: List = [None] * len(shape)
            used: Dict[int, List[str]] = {d: [] for d in dims}
            divisor: Dict[int, int] = {d: 1 for d in dims}
            for a in sorted(self.mesh.axis_names, key=lambda a: -_axis_size(self.mesh, a)):
                sz = _axis_size(self.mesh, a)
                if sz <= 1:
                    continue
                for d in dims:
                    if shape[d] % (divisor[d] * sz) == 0:
                        used[d].append(a)
                        divisor[d] *= sz
                        break
            for d in dims:
                if used[d]:
                    spec[d] = tuple(used[d]) if len(used[d]) > 1 else used[d][0]
            return P(*spec)
        base = tuple(self.param_spec(path, shape))
        if not self.zero1:
            return P(*base)
        return P(*self._add_data_sharding(base, shape, path + "(opt)"))

    # ---- activations / batches ---------------------------------------------------
    def batch_spec(self, shape: Tuple[int, ...], *, batch_dim: int = 0) -> P:
        spec: List = [None] * len(shape)
        axes = self._data_axis_for(shape[batch_dim], "batch")
        spec[batch_dim] = axes
        return P(*spec)

    def activation_spec(self, shape: Tuple[int, ...]) -> P:
        # [B, S, D] — batch over dp, D over model when divisible
        spec: List = [None] * len(shape)
        spec[0] = self._data_axis_for(shape[0], "act-batch")
        return P(*spec)

    def logits_spec(self, shape: Tuple[int, ...]) -> P:
        spec: List = [None] * len(shape)
        spec[0] = self._data_axis_for(shape[0], "logits-batch")
        spec[-1] = self._shard_if(shape[-1], "model", "logits-vocab")
        return P(*spec)

    # ---- KV caches ------------------------------------------------------------
    def cache_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        """KVCache leaves: [n_periods, B, W, Hkv, hd] (k/v) or
        [n_periods, B, W] (pos); SSM states [n_periods, B, H, P, N]."""
        name = path.rsplit("/", 1)[-1]
        n = len(shape)
        spec: List = [None] * n
        is_kv = name in ("k", "v") and n == 5  # [np, B, W, Hkv, hd]
        is_pos = name == "pos" and n == 3  # [np, B, W]
        if n >= 2:
            bdim = 1  # batch dim (after the stacked periods dim)
            if shape[bdim] > 1:
                spec[bdim] = self._data_axis_for(shape[bdim], "cache-batch")
            elif self.seq_shard_cache and (is_kv or is_pos):
                # long_500k: batch 1 ⇒ shard the cache *sequence* over data
                spec[2] = self._data_axis_for(shape[2], "cache-seq")
        if is_kv or is_pos:
            heads_ok = (
                self.cache_kv_heads is not None
                and self.model_size > 1
                and self.cache_kv_heads % self.model_size == 0
            )
            if is_kv and heads_ok:
                spec[3] = "model"
            elif spec[2] is None and shape[2] % max(self.model_size, 1) == 0 and self.model_size > 1:
                # KV heads don't divide the model axis (e.g. 8 heads / 16-way
                # TP): split the cache *sequence* over 'model' instead —
                # flash-decoding-style split-K attention (partial softmax
                # combined by collectives the partitioner inserts). Applied
                # to k/v AND pos so the masking stays aligned.
                spec[2] = "model"
                self.fallbacks.append(
                    f"{path}: kv heads !% model — cache seq sharded on model"
                )
        if name == "h" and n == 5:  # SSM state [np, B, H, P, N]
            spec[2] = self._shard_if(shape[2], "model", "ssm-state-heads")
        if name == "conv_x" and n == 4:  # [np, B, K-1, di]
            spec[3] = self._shard_if(shape[3], "model", "conv-tail")
        return P(*spec)

    def explain(self) -> List[str]:
        return list(dict.fromkeys(self.fallbacks))


# ---------------------------------------------------------------------------
# tree application
# ---------------------------------------------------------------------------


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def tree_specs(tree, spec_fn) -> Any:
    """Map (path, shape) → PartitionSpec over a pytree of arrays or
    ShapeDtypeStructs."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: spec_fn(_path_str(path), tuple(leaf.shape)), tree
    )


def tree_shardings(tree, mesh: Mesh, spec_fn) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_fn(_path_str(path), tuple(leaf.shape))),
        tree,
    )
