# Device copies of table columns kept between queries.  A table registered
# with a session does not change until it is replaced, dropped or (under
# ``revalidate='content'``) edited in place, so the monolithic executor
# places each column it reads once and later plans read the same device
# array instead of copying the host column again.
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional, Tuple

import numpy as np

import jax

from repro.data.multiset import Database

# (Multiset.uid, field, placement): placement is None for the default
# device, else the NamedSharding the columns are split by
Key = Tuple[int, str, Optional[Hashable]]


class ResidentColumns:
    """The device columns of one ``Database``, least recently used first.

    Resident bytes on each device are kept under half the device's
    ``memory_stats()["bytes_limit"]``; the other half is the programs' own.
    Past that bound the least recently used columns the current query does
    not read are dropped, and a column that still does not fit is placed
    for its query only.  A device that reports no limit (the CPU) has no
    bound.  ``_budget`` overrides the bound, in bytes per device.

    The store holds the columns of one stats epoch of its database: a
    session ``sync``s it to the epoch it plans under before each run, and a
    new epoch (a table added, replaced, dropped or edited) empties it."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._arrays: "OrderedDict[Key, jax.Array]" = OrderedDict()
        self._bytes: Dict[Key, Dict[Any, int]] = {}  # key -> device -> bytes
        self._limits: Dict[Any, Optional[int]] = {}
        self._budget: Optional[int] = None
        self._epoch: Optional[str] = None

    def __len__(self) -> int:
        return len(self._arrays)

    def fetch(self, wanted: Dict[Key, Callable[[], jax.Array]]) -> Dict[Key, Tuple[jax.Array, bool]]:
        """The device array of each wanted column and whether it was
        resident; a missing one is placed by its callable, under the lock,
        so concurrent plans reading one column place it once."""
        out: Dict[Key, Tuple[jax.Array, bool]] = {}
        with self._lock:
            for key, place in wanted.items():
                arr = self._arrays.pop(key, None)
                hit = arr is not None
                if arr is None:
                    arr = place()
                    self._bytes[key] = _device_bytes(arr)
                self._arrays[key] = arr  # most recently used last
                out[key] = (arr, hit)
                if not hit:
                    self._fit(reading=wanted.keys(), new=key)
        return out

    def sync(self, epoch: str) -> None:
        with self._lock:
            if epoch != self._epoch:
                self._arrays.clear()
                self._bytes.clear()
                self._epoch = epoch

    def clear(self) -> None:
        self.sync("")  # no session plans under the empty epoch

    def _limit(self, device: Any) -> Optional[int]:
        if self._budget is not None:
            return self._budget
        if device not in self._limits:
            stats = device.memory_stats() or {}
            limit = stats.get("bytes_limit")
            self._limits[device] = None if limit is None else int(limit) // 2
        return self._limits[device]

    def _fit(self, reading: Any, new: Key) -> None:
        used: Dict[Any, int] = {}
        for per_device in self._bytes.values():
            for d, n in per_device.items():
                used[d] = used.get(d, 0) + n

        def over() -> bool:
            return any(n > lim for d, n in used.items() if (lim := self._limit(d)) is not None)

        for key in [k for k in self._arrays if k not in reading]:
            if not over():
                return
            self._drop(key, used)
        if over():
            self._drop(new, used)

    def _drop(self, key: Key, used: Dict[Any, int]) -> None:
        del self._arrays[key]
        for d, n in self._bytes.pop(key).items():
            used[d] -= n


def _device_bytes(arr: jax.Array) -> Dict[Any, int]:
    s = arr.sharding
    n = int(np.prod(s.shard_shape(arr.shape))) * arr.dtype.itemsize
    return {d: n for d in s.addressable_devices}


_CREATE_LOCK = threading.Lock()


def resident_columns(db: Database) -> ResidentColumns:
    """The one store of ``db`` (made on first use): sessions that share a
    database (a ``QueryServer``'s tenants) share its device columns."""
    with _CREATE_LOCK:
        if db.resident is None:
            db.resident = ResidentColumns()
        return db.resident
