"""Per-store serving counters: lookups, batches, bytes, latency percentiles.

Counts are exact and cumulative; a reader takes deltas between two
snapshots. Where the decode copies, they count what it copies: the bytes
of every array a device batch copied in and read back, the tokens that
reached a decode and the dictionary rows the kernel gathered for them, and
the strings each path decoded.
"""

from __future__ import annotations

from repro.obs import REGISTRY, Counter, Histogram


class StoreStats:
    """Mutable counters updated by the store's hot path."""

    def __init__(self, backend: str = "unknown") -> None:
        self.lookups = 0            # ids requested (incl. duplicates/cached)
        self.decoded_strings = 0    # strings actually decoded (cache misses)
        self.decoded_bytes = 0
        self.batches = 0            # kernel/numpy decode invocations
        self.padded_rows = 0        # batch rows incl. padding (waste metric)
        self.real_tokens = 0        # tokens of the strings that reached a decode
        self.dict_rows = 0          # 16-byte dictionary rows the kernel gathered
        self.h2d_bytes = 0          # bytes of the arrays copied to the device
        self.d2h_bytes = 0          # bytes of the arrays read back
        self.device_strings = 0     # strings the kernel decoded
        self.host_strings = 0       # strings the numpy or cold-tier path decoded
        #: decode invocations per (B, T) shape
        self.batches_by_shape: dict[tuple[int, int], int] = {}
        self.scan_strings = 0
        self.cold_lookups = 0       # misses decoded from the RLZ cold tier
        self.locates = 0            # reverse lookups (queries, incl. misses)
        self.locate_hits = 0        # reverse lookups that found an id
        self.prefix_scans = 0       # scan_prefix calls
        self.jit_shapes: set[tuple[int, int]] = set()  # (B, T) decode shapes
        #: seconds of the first decode batch of each jit shape: its compile
        #: (or compile-cache load) plus one run
        self.first_batch_s: dict[tuple[int, int], float] = {}
        # per-store instruments (snapshot() stays instance-scoped) registered
        # into the process registry, labelled by the resolved decode backend
        labels = {"backend": backend}
        self._lat = REGISTRY.register(Histogram(
            "repro_store_multiget_latency_us", labels=labels))
        self._lookups_total = REGISTRY.register(Counter(
            "repro_store_lookups_total", labels=labels))
        self._locate_lat = REGISTRY.register(Histogram(
            "repro_store_locate_latency_us", labels=labels))

    # ------------------------------------------------------------- recording
    def record_multiget(self, n_ids: int, seconds: float) -> None:
        self.lookups += n_ids
        self._lookups_total.inc(n_ids)
        self._lat.record_seconds(seconds)

    def record_locate(self, n_queries: int, n_hits: int,
                      seconds: float) -> None:
        self.locates += n_queries
        self.locate_hits += n_hits
        self._locate_lat.record_seconds(seconds)

    def record_decode_batch(self, shape: tuple[int, int], n_real: int,
                            nbytes: int, n_tokens: int, seconds: float,
                            device: bool, h2d_bytes: int = 0,
                            d2h_bytes: int = 0, n_rows: int = 0) -> None:
        """One decode batch; a device batch gathered ``n_rows`` dictionary
        rows (one a token of OnPair16)."""
        self.batches += 1
        self.padded_rows += shape[0]
        self.decoded_strings += n_real
        self.decoded_bytes += nbytes
        self.real_tokens += n_tokens
        self.batches_by_shape[shape] = self.batches_by_shape.get(shape, 0) + 1
        if device:
            self.device_strings += n_real
            self.dict_rows += n_rows
            self.h2d_bytes += h2d_bytes
            self.d2h_bytes += d2h_bytes
            self.first_batch_s.setdefault(shape, seconds)
            self.jit_shapes.add(shape)
        else:
            self.host_strings += n_real

    # ------------------------------------------------------------- reporting
    def snapshot(self, cache_stats: dict | None = None) -> dict:
        lat = self._lat.summary()
        return {
            "lookups": self.lookups,
            "decoded_strings": self.decoded_strings,
            "decoded_bytes": self.decoded_bytes,
            "scan_strings": self.scan_strings,
            "cold_lookups": self.cold_lookups,
            "locates": self.locates,
            "locate_hits": self.locate_hits,
            "prefix_scans": self.prefix_scans,
            "batches": self.batches,
            "padded_rows": self.padded_rows,
            "real_tokens": self.real_tokens,
            "dict_rows": self.dict_rows,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "device_strings": self.device_strings,
            "host_strings": self.host_strings,
            "batches_by_shape": {f"{b}x{t}": n for (b, t), n
                                 in sorted(self.batches_by_shape.items())},
            "pad_efficiency": round(
                self.decoded_strings / self.padded_rows, 4
            ) if self.padded_rows else 1.0,
            "jit_shapes": sorted(self.jit_shapes),
            "first_batch_s": {f"{b}x{t}": round(sec, 4) for (b, t), sec
                              in sorted(self.first_batch_s.items())},
            "multiget_latency": lat,
            "multiget_latency_hist": self._lat.state(),
            "cache": cache_stats or {},
        }
