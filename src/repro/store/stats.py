"""Per-store serving counters: lookups, batches, bytes, latency percentiles.

Latency/percentile math lives in ``repro.core.metrics`` (latency_summary /
throughput_mib_s) so the store, the service layer, and the benchmark harness
all report identical definitions of p50/p99 and MiB/s.
"""

from __future__ import annotations

import time

from repro.core.metrics import throughput_mib_s
from repro.obs import REGISTRY, Counter, Histogram


class StoreStats:
    """Mutable counters updated by the store's hot path."""

    def __init__(self, backend: str = "unknown") -> None:
        self.started_at = time.perf_counter()
        self.lookups = 0            # ids requested (incl. duplicates/cached)
        self.decoded_strings = 0    # strings actually decoded (cache misses)
        self.decoded_bytes = 0
        self.batches = 0            # kernel/numpy decode invocations
        self.padded_rows = 0        # batch rows incl. padding (waste metric)
        self.decode_seconds = 0.0
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
                            nbytes: int, seconds: float,
                            jitted: bool) -> None:
        self.batches += 1
        self.padded_rows += shape[0]
        self.decoded_strings += n_real
        self.decoded_bytes += nbytes
        self.decode_seconds += seconds
        if jitted:
            self.first_batch_s.setdefault(shape, seconds)
            self.jit_shapes.add(shape)

    # ------------------------------------------------------------- reporting
    def snapshot(self, cache_stats: dict | None = None) -> dict:
        elapsed = time.perf_counter() - self.started_at
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
            "pad_efficiency": round(
                self.decoded_strings / self.padded_rows, 4
            ) if self.padded_rows else 1.0,
            "jit_shapes": sorted(self.jit_shapes),
            "first_batch_s": {f"{b}x{t}": round(sec, 4) for (b, t), sec
                              in sorted(self.first_batch_s.items())},
            "decode_mib_s": round(
                throughput_mib_s(self.decoded_bytes, self.decode_seconds), 2
            ) if self.decode_seconds else 0.0,
            "lookups_per_s": round(self.lookups / elapsed, 1) if elapsed else 0.0,
            "multiget_latency": lat,
            "multiget_latency_hist": self._lat.state(),
            "cache": cache_stats or {},
        }
