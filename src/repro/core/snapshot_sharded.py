"""Sharded replica snapshots: one shard per GRIS/GIIS registrant.

The flat :class:`~repro.core.snapshot.ReplicaSnapshot` is rebuilt whole
when an epoch rolls. A :class:`ShardedSnapshot` partitions the replica
rows along the information-service topology, so that one site's
dynamic-attribute refresh touches only that site's rows:

  * rows are grouped into named shards (per-GRIS / per-GIIS-registrant)
    over ONE shared attribute vocabulary,
  * **delta refresh**: ``update_rows``/``refresh`` refill only the shards
    whose entries changed, and ``shard_epochs[g]`` bumps per changed
    shard; ``refresh_from_giis`` re-reads only the registrants whose
    GIIS epoch moved,
  * :meth:`~ShardedSnapshot.device_columns` is the one device view: the
    live rows of every shard as the flat snapshot's padded ``[S_PAD,
    A_PAD]`` block, built lazily once per version. Every broker, flat or
    sharded, answers through the same candidate launch over it.

The global row space is the shard-major concatenation of live rows (shard
order = sorted shard names), so brokers keep using plain integer rows;
``shard_of_row``/``offsets`` translate between the two.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .compile import ColumnTable
from .snapshot import _round_up, entry_row, numeric_attr_names

__all__ = ["ShardedSnapshot", "shard_by_hash"]

def shard_by_hash(key: str, n_shards: int) -> int:
    """Deterministic endpoint→shard bucket (crc32 — platform-stable,
    unlike ``hash()`` under PYTHONHASHSEED)."""
    return zlib.crc32(key.encode("utf-8")) % max(1, int(n_shards))


class ShardedSnapshot:
    """Per-registrant sharded candidate table, padded and device-resident.

    Parameters
    ----------
    shard_entries:
        shard name → list of flattened GRIS views (one per candidate
        row). Shard order is ``sorted(shard_entries)``; the global row
        space concatenates the shards in that order.
    attr_names:
        Shared column vocabulary (lower-cased). Defaults to the union of
        numeric attributes across *all* shards.
    block_s:
        Row padding granularity per shard (the kernel's S-block).
    device:
        Hand out :meth:`device_columns` as ``jax.Array``s (numpy blocks
        otherwise).
    """

    def __init__(
        self,
        shard_entries: Mapping[str, Sequence[Mapping[str, Any]]],
        attr_names: Optional[Sequence[str]] = None,
        *,
        block_s: int = 512,
        device: bool = True,
        epoch: int = 0,
    ):
        if not shard_entries:
            raise ValueError("ShardedSnapshot needs at least one shard")
        self.shard_names: List[str] = sorted(shard_entries)
        self.entries_by_shard: Dict[str, List[Dict[str, Any]]] = {
            name: [dict(e) for e in shard_entries[name]] for name in self.shard_names
        }
        all_entries = [
            e for name in self.shard_names for e in self.entries_by_shard[name]
        ]
        if attr_names is None:
            attr_names = numeric_attr_names(all_entries)
        self.attr_names: List[str] = [n.lower() for n in attr_names]
        self._index = {n: j for j, n in enumerate(self.attr_names)}
        self.block_s = int(block_s)
        self.epoch = int(epoch)
        self.version = 0  # bumped on every mutation
        self._device = bool(device)

        self.g = len(self.shard_names)
        self.counts = np.array(
            [len(self.entries_by_shard[n]) for n in self.shard_names], dtype=np.int64
        )
        self.offsets = np.zeros((self.g,), dtype=np.int64)
        np.cumsum(self.counts[:-1], out=self.offsets[1:])
        self.n = int(self.counts.sum())
        a = len(self.attr_names)
        self.a_pad = max(_round_up(a, 128), 128)
        max_count = int(self.counts.max()) if self.g else 1
        self.s_shard_pad = max(_round_up(max(max_count, 1), self.block_s), self.block_s)

        self._attrs = np.zeros((self.g, self.s_shard_pad, self.a_pad), np.float32)
        self._valid = np.zeros((self.g, self.s_shard_pad, self.a_pad), np.float32)
        for gi in range(self.g):
            self._fill_shard_host(gi)

        #: per-shard delta-refresh counters: which shards each
        #: ``update_rows``/``refresh`` changed
        self.shard_epochs = np.zeros((self.g,), dtype=np.int64)
        self._shard_logical: List[Optional[Tuple[int, np.ndarray, np.ndarray]]] = [
            None
        ] * self.g
        self._flat_dev = None  # lazy (version, attrs, valid) padded block

    # ------------------------------------------------------------- building
    def _fill_shard_host(self, gi: int) -> None:
        name = self.shard_names[gi]
        self._attrs[gi] = 0.0
        self._valid[gi] = 0.0
        for li, e in enumerate(self.entries_by_shard[name]):
            vals, ok = entry_row(e, self._index, self.a_pad)
            self._attrs[gi, li] = vals
            self._valid[gi, li] = ok

    # ------------------------------------------------------------ accessors
    def shard_of_row(self, row: int) -> int:
        """Global row index → owning shard index."""
        if not (0 <= row < self.n):
            raise IndexError(f"row {row} outside snapshot (n={self.n})")
        return int(np.searchsorted(self.offsets, row, side="right") - 1)

    def device_columns(self):
        """→ (attrs [S_PAD, A_PAD], valid, n): the live rows of every
        shard concatenated in global row order and padded as the flat
        :class:`ReplicaSnapshot` pads them — the candidate launch's
        operand. Materialized lazily, cached per version."""
        hit = self._flat_dev
        if hit is not None and hit[0] == self.version:
            return hit[1], hit[2], self.n
        attrs_l, valid_l = self.logical_columns()
        s_pad = max(_round_up(max(self.n, 1), self.block_s), self.block_s)
        attrs = np.zeros((s_pad, self.a_pad), np.float32)
        valid = np.zeros((s_pad, self.a_pad), np.float32)
        a = len(self.attr_names)
        attrs[: self.n, :a] = attrs_l
        valid[: self.n, :a] = valid_l
        if self._device:
            import jax.numpy as jnp

            attrs, valid = jnp.asarray(attrs), jnp.asarray(valid)
        self._flat_dev = (self.version, attrs, valid)
        return attrs, valid, self.n

    def shard_logical_columns(self, gi: int) -> Tuple[np.ndarray, np.ndarray]:
        """→ contiguous (attrs [c_g, A] f32, valid [c_g, A] bool) over one
        shard's live rows at logical width. Cached per (shard,
        shard_epoch)."""
        hit = self._shard_logical[gi]
        if hit is not None and hit[0] == self.shard_epochs[gi]:
            return hit[1], hit[2]
        a = len(self.attr_names)
        c = int(self.counts[gi])
        attrs = np.ascontiguousarray(self._attrs[gi, :c, :a])
        valid = np.ascontiguousarray(self._valid[gi, :c, :a] > 0.5)
        self._shard_logical[gi] = (int(self.shard_epochs[gi]), attrs, valid)
        return attrs, valid

    def logical_columns(self) -> Tuple[np.ndarray, np.ndarray]:
        """Global contiguous (attrs [n, A] f32, valid [n, A] bool) in
        shard-major row order."""
        hit = getattr(self, "_logical", None)
        if hit is not None and hit[0] == self.version:
            return hit[1], hit[2]
        parts = [self.shard_logical_columns(gi) for gi in range(self.g)]
        attrs = (
            np.concatenate([p[0] for p in parts])
            if self.n
            else np.zeros((0, len(self.attr_names)), np.float32)
        )
        valid = (
            np.concatenate([p[1] for p in parts])
            if self.n
            else np.zeros((0, len(self.attr_names)), bool)
        )
        self._logical = (self.version, attrs, valid)
        return attrs, valid

    def table(self) -> ColumnTable:
        """f64 :class:`ColumnTable` over the global live rows — same
        semantics as the flat snapshot's."""
        attrs, valid = self.logical_columns()
        tbl = ColumnTable(self.n)
        for name, j in self._index.items():
            tbl.add(name, attrs[:, j].astype(np.float64), valid[:, j].copy())
        return tbl

    def vocab_key(self) -> Tuple[str, ...]:
        return tuple(self.attr_names)

    # ------------------------------------------------------------ mutation
    def update_rows(self, updates: Mapping[int, Mapping[str, Any]]) -> List[int]:
        """Incremental refresh keyed by *global* row: merge attribute
        dicts into existing rows. Returns the dirty shard indices."""
        if not updates:
            return []
        from .snapshot import _numeric
        import math

        rows_sorted = np.fromiter(sorted(updates), dtype=np.int64, count=len(updates))
        if int(rows_sorted[0]) < 0 or int(rows_sorted[-1]) >= self.n:
            bad = rows_sorted[0] if rows_sorted[0] < 0 else rows_sorted[-1]
            raise IndexError(f"row {int(bad)} outside snapshot (n={self.n})")
        gis = np.searchsorted(self.offsets, rows_sorted, side="right") - 1
        dirty: Dict[int, bool] = {}
        for row, gi in zip(rows_sorted.tolist(), gis.tolist()):
            name = self.shard_names[gi]
            li = row - int(self.offsets[gi])
            entry = self.entries_by_shard[name][li]
            upd = updates[row]
            # spelling-aware merge: attribute names are case-insensitive
            # (ClassAd semantics), so an update must overwrite the
            # resident spelling, not add a second key for the same column
            lower_of = {kk.lower(): kk for kk in entry}
            for k, v in upd.items():
                kk = lower_of.setdefault(k.lower(), k)
                entry[kk] = v
            # scalar fast path: a purely numeric in-vocabulary update can
            # write its cells directly — exact vs an entry_row recompute
            # as long as no two entry spellings collide on a column
            fast = len(entry) == len(lower_of)
            if fast:
                for k, v in upd.items():
                    j = self._index.get(k.lower())
                    x = _numeric(v)
                    if j is None or x is None or not math.isfinite(x):
                        fast = False
                        break
                    self._attrs[gi, li, j] = np.float32(x)
                    self._valid[gi, li, j] = 1.0
            if not fast:
                vals, ok = entry_row(entry, self._index, self.a_pad)
                self._attrs[gi, li] = vals
                self._valid[gi, li] = ok
            dirty[gi] = True
        changed = sorted(dirty)
        self.shard_epochs[changed] += 1
        self.version += 1
        return changed

    def refresh(
        self, shard_entries: Mapping[str, Sequence[Mapping[str, Any]]]
    ) -> List[str]:
        """Epoch roll with delta detection: compare each shard's new entry
        list against the resident one; only *changed* shards are refilled.
        Returns the changed shard names.

        Raises ``ValueError`` when the shard set or a shard's row count
        changed, or when a new numeric attribute falls outside the shared
        vocabulary — those alter the row space / column space, and the
        caller must fall back to a full rebuild (:meth:`new_epoch`).
        """
        if sorted(shard_entries) != self.shard_names:
            raise ValueError("shard set changed — rebuild with new_epoch()")
        changed: List[str] = []
        new_lists: Dict[str, List[Dict[str, Any]]] = {}
        for name in self.shard_names:
            new = shard_entries[name]
            old = self.entries_by_shard[name]
            if new is old:
                continue
            new_list = [dict(e) for e in new]
            if new_list == old:
                continue
            if len(new_list) != len(old):
                raise ValueError(
                    f"shard {name!r} row count changed "
                    f"({len(old)} → {len(new_list)}) — rebuild with new_epoch()"
                )
            for e in new_list:
                for k, v in e.items():
                    if (
                        k.lower() not in self._index
                        and isinstance(v, (bool, int, float))
                    ):
                        raise ValueError(
                            f"attribute {k!r} outside the shared vocabulary "
                            "— rebuild with new_epoch()"
                        )
            new_lists[name] = new_list
            changed.append(name)
        self.epoch += 1
        if not changed:
            return []
        dirty = []
        for name in changed:
            gi = self.shard_names.index(name)
            self.entries_by_shard[name] = new_lists[name]
            self._fill_shard_host(gi)
            dirty.append(gi)
        self.shard_epochs[dirty] += 1
        self.version += 1
        return changed

    def new_epoch(
        self,
        shard_entries: Mapping[str, Sequence[Mapping[str, Any]]],
        *,
        reuse_vocab: bool = True,
    ) -> "ShardedSnapshot":
        """Full rebuild for a structurally changed epoch (new shard set,
        grown shards, vocabulary drift)."""
        return ShardedSnapshot(
            shard_entries,
            self.attr_names if reuse_vocab else None,
            block_s=self.block_s,
            device=self._device,
            epoch=self.epoch + 1,
        )

    # -------------------------------------------------------- GIIS bridge
    @classmethod
    def from_giis(cls, giis, **kwargs) -> "ShardedSnapshot":
        """Build one shard per GIIS registrant (the paper's topology: one
        GRIS per storage site, aggregated by the index)."""
        shard_entries = {
            name: giis.registrant_entries(name) for name in giis.registrants()
        }
        snap = cls(shard_entries, **kwargs)
        snap._giis_epochs = dict(giis.registrant_epochs())
        return snap

    def refresh_from_giis(self, giis) -> List[str]:
        """Delta refresh driven by the GIIS's per-registrant epoch
        counters: only registrants whose epoch moved are re-read. Raises
        ``ValueError`` (like :meth:`refresh`) when the topology changed
        structurally."""
        prev = getattr(self, "_giis_epochs", {})
        now_epochs = giis.registrant_epochs(refresh=True)
        if sorted(now_epochs) != self.shard_names:
            raise ValueError("GIIS registrant set changed — rebuild with new_epoch()")
        payload: Dict[str, Sequence[Mapping[str, Any]]] = {}
        for name in self.shard_names:
            if now_epochs[name] != prev.get(name):
                payload[name] = giis.registrant_entries(name)
            else:
                payload[name] = self.entries_by_shard[name]  # identity ⇒ skipped
        changed = self.refresh(payload)
        self._giis_epochs = dict(now_epochs)
        return changed

    def __len__(self) -> int:
        return self.n

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (
            f"ShardedSnapshot(g={self.g}, n={self.n}, a={len(self.attr_names)}, "
            f"pad=[{self.s_shard_pad},{self.a_pad}], epoch={self.epoch}, "
            f"version={self.version})"
        )
