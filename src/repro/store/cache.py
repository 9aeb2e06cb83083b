"""Byte-budgeted LRU cache for decoded strings, with hit/miss accounting.

Point-lookup traffic against a compressed store is typically heavily skewed
(Zipfian ids); caching decoded strings turns the common case into a dict hit
and leaves the Pallas batch decoder serving the miss tail. Capacity is in
*decoded payload bytes* so the resident budget is explicit next to the
compressed corpus's own footprint.
"""

from __future__ import annotations

from collections import OrderedDict


class LRUCache:
    """LRU over ``int id -> bytes`` with a decoded-bytes capacity budget.

    ``capacity_bytes=0`` disables caching (every get misses, puts drop) —
    used by benchmarks to measure the pure decode path.
    """

    def __init__(self, capacity_bytes: int = 8 << 20):
        self.capacity_bytes = int(capacity_bytes)
        # oldest first; an OrderedDict's links make eviction and the recency
        # bump O(1), where a plain dict's next(iter()) walks the holes that
        # earlier evictions left at its front
        self._data: OrderedDict[int, bytes] = OrderedDict()
        self.current_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key: int) -> bool:
        return key in self._data

    _MISSING = object()  # sentinel: b"" is a valid cached value

    def get(self, key: int) -> bytes | None:
        val = self._data.get(key, self._MISSING)
        if val is self._MISSING:
            self.misses += 1
            return None
        self._data.move_to_end(key)
        self.hits += 1
        return val

    def put(self, key: int, value: bytes) -> None:
        if self.capacity_bytes <= 0:
            return
        if len(value) > self.capacity_bytes:
            # never admit an entry the budget can't hold: it would evict the
            # whole cache and then pin current_bytes over capacity forever
            return
        data = self._data
        old = data.get(key)
        if old is not None:
            self.current_bytes -= len(old)
            data.move_to_end(key)
        data[key] = value
        self.current_bytes += len(value)
        while self.current_bytes > self.capacity_bytes and len(data) > 1:
            self.current_bytes -= len(data.popitem(last=False)[1])
            self.evictions += 1

    def clear(self) -> None:
        self._data.clear()
        self.current_bytes = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {"entries": len(self._data), "bytes": self.current_bytes,
                "capacity_bytes": self.capacity_bytes, "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "hit_rate": round(self.hit_rate, 4)}
