"""Device-resident replica snapshots: build the columnar table ONCE per
GRIS/GIIS epoch, keep it on-device, update rows incrementally.

The paper's broker re-reads the information service on every selection;
our fleet scenario has thousands of concurrent selections against the
*same published snapshot* of GRIS state. The per-call costs that
dominated the old path — numpy ``pad_columns`` + a fresh [S_PAD, A_PAD]
host→device transfer per ``matchrank`` call — are paid here exactly once
per epoch:

  * numeric attributes of all entries are columnarized (f64 ``ColumnTable``
    for the columnar/policy programs — bit-identical broker semantics),
  * the f32 [S_PAD, A_PAD] attrs/valid blocks are padded to lane/sublane
    alignment and pushed to the device as ``jax.Array``s,
  * dynamic-attribute refreshes between epochs are applied as *row
    updates* (``update_rows``) — an O(rows_changed) ``.at[].set`` instead
    of an O(S·A) rebuild,
  * every mutation bumps ``version`` so plan/launch caches can invalidate.

``matchrank``/``matchrank_batched`` accept the snapshot's pre-padded
device blocks directly (``n_rows`` marks the live prefix), so the steady
state ships only the tiny per-request plan tensors per launch.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .compile import ColumnTable

__all__ = ["ReplicaSnapshot", "entry_row", "numeric_attr_names"]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _numeric(v: Any) -> Optional[float]:
    """ClassAd-compatible numeric coercion (bool counts as a number)."""
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    if isinstance(v, (int, float)):
        return float(v)
    return None


def numeric_attr_names(entries: Sequence[Mapping[str, Any]]) -> List[str]:
    """The sorted union of attribute names that are numeric in at least
    one entry — the snapshot's column vocabulary."""
    names = set()
    for e in entries:
        for k, v in e.items():
            if _numeric(v) is not None:
                names.add(k.lower())
    return sorted(names)


def entry_row(
    entry: Mapping[str, Any], index: Mapping[str, int], a_pad: int
) -> Tuple[np.ndarray, np.ndarray]:
    """One entry's (vals, ok) column vectors over a vocabulary index —
    the row-fill semantics shared by the flat and sharded snapshots."""
    vals = np.zeros((a_pad,), dtype=np.float32)
    ok = np.zeros((a_pad,), dtype=np.float32)
    for k, v in entry.items():
        j = index.get(k.lower())
        if j is None:
            continue
        x = _numeric(v)
        if x is None or not math.isfinite(x):
            continue  # NaN/inf publishes as Undefined, not a poisoned cell
        vals[j] = np.float32(x)
        ok[j] = 1.0
    return vals, ok


class ReplicaSnapshot:
    """One GRIS epoch's candidate table, padded and device-resident.

    Parameters
    ----------
    entries:
        One flattened GRIS view (attribute dict) per candidate row. Row
        order is the snapshot's candidate index space.
    attr_names:
        Column vocabulary (lower-cased, ordered). Defaults to the union
        of numeric attributes across ``entries`` — pass an explicit
        vocabulary to keep plans reusable across epochs whose attribute
        sets drift.
    block_s:
        Row padding granularity (the kernel's S-block).
    device:
        Keep the padded f32 blocks resident as ``jax.Array``s. With
        ``device=False`` the snapshot is numpy-only (no jax import cost),
        still amortizing the pad.
    """

    def __init__(
        self,
        entries: Sequence[Mapping[str, Any]],
        attr_names: Optional[Sequence[str]] = None,
        *,
        block_s: int = 512,
        device: bool = True,
        epoch: int = 0,
    ):
        self.entries: List[Dict[str, Any]] = [dict(e) for e in entries]
        if attr_names is None:
            attr_names = numeric_attr_names(self.entries)
        self.attr_names: List[str] = [n.lower() for n in attr_names]
        self._index = {n: j for j, n in enumerate(self.attr_names)}
        self.block_s = int(block_s)
        self.epoch = int(epoch)
        self.version = 0  # bumped on every mutation (epoch or row update)
        self._device = bool(device)

        n = len(self.entries)
        a = len(self.attr_names)
        self.n = n
        self.a_pad = max(_round_up(a, 128), 128)
        self.s_pad = max(_round_up(max(n, 1), self.block_s), self.block_s)

        self._attrs = np.zeros((self.s_pad, self.a_pad), dtype=np.float32)
        self._valid = np.zeros((self.s_pad, self.a_pad), dtype=np.float32)
        for i, e in enumerate(self.entries):
            self._fill_row_host(i, e)
        self._attrs_dev = None
        self._valid_dev = None
        if self._device:
            self._push_all()

    # ------------------------------------------------------------- building
    def _row_vectors(self, entry: Mapping[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
        return entry_row(entry, self._index, self.a_pad)

    def _fill_row_host(self, i: int, entry: Mapping[str, Any]) -> None:
        vals, ok = self._row_vectors(entry)
        self._attrs[i] = vals
        self._valid[i] = ok

    def _push_all(self) -> None:
        import jax.numpy as jnp

        self._attrs_dev = jnp.asarray(self._attrs)
        self._valid_dev = jnp.asarray(self._valid)

    # ------------------------------------------------------------ accessors
    def device_columns(self):
        """→ (attrs, valid, n_rows): the padded candidate block (device-
        resident when built with ``device=True``)."""
        if self._attrs_dev is not None:
            return self._attrs_dev, self._valid_dev, self.n
        return self._attrs, self._valid, self.n

    def host_columns(self) -> Tuple[np.ndarray, np.ndarray, int]:
        return self._attrs, self._valid, self.n

    def table(self) -> ColumnTable:
        """An f64 :class:`ColumnTable` over the live rows — the operand of
        columnar programs and compiled server policies (numpy semantics
        identical to the per-request broker path)."""
        tbl = ColumnTable(self.n)
        for name, j in self._index.items():
            tbl.add(
                name,
                self._attrs[: self.n, j].astype(np.float64),
                self._valid[: self.n, j] > 0.5,
            )
        return tbl

    def vocab_key(self) -> Tuple[str, ...]:
        """Hashable vocabulary identity for plan caching."""
        return tuple(self.attr_names)

    # ------------------------------------------------------------ mutation
    def update_rows(self, updates: Mapping[int, Mapping[str, Any]]) -> None:
        """Incremental refresh: merge attribute dicts into existing rows.

        This is the between-epoch path for dynamic attributes (load
        factor, available space, bandwidth EWMAs): O(rows_changed) host
        work and ONE scatter per call on device, no table rebuild.
        """
        if not updates:
            return
        rows = sorted(updates)
        for i in rows:
            if not (0 <= i < self.n):
                raise IndexError(f"row {i} outside snapshot (n={self.n})")
            self.entries[i].update(updates[i])
            self._fill_row_host(i, self.entries[i])
        if self._attrs_dev is not None:
            import jax.numpy as jnp

            idx = np.asarray(rows, dtype=np.int32)
            new_attrs = jnp.asarray(self._attrs[idx])
            new_valid = jnp.asarray(self._valid[idx])
            self._attrs_dev = self._attrs_dev.at[idx].set(new_attrs)
            self._valid_dev = self._valid_dev.at[idx].set(new_valid)
        self.version += 1

    def new_epoch(
        self, entries: Sequence[Mapping[str, Any]], *, reuse_vocab: bool = True
    ) -> "ReplicaSnapshot":
        """A full rebuild for the next published GRIS epoch."""
        return ReplicaSnapshot(
            entries,
            self.attr_names if reuse_vocab else None,
            block_s=self.block_s,
            device=self._device,
            epoch=self.epoch + 1,
        )

    def __len__(self) -> int:
        return self.n

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"ReplicaSnapshot(n={self.n}, a={len(self.attr_names)}, "
            f"pad=[{self.s_pad},{self.a_pad}], epoch={self.epoch}, "
            f"version={self.version}, device={self._attrs_dev is not None})"
        )
