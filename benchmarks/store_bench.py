"""Store serving benchmark: batched multiget vs the naive per-string loop.

Measures, over uniform random ids on one dataset:

* ``naive``      — per-string ``OnPairCompressor.access`` loop (the paper's
                   random-access microbenchmark, one string per call);
* ``store-*``    — ``CompressedStringStore.multiget`` in serving-sized
                   batches through each available backend (cache disabled so
                   the decode path is what's timed).

Emits the harness JSON schema (list of row dicts under results/bench) with
throughput (lookups/s, MiB/s) and p50/p99 latency per batch from
``repro.core.metrics.latency_summary``.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks.common import dataset
from repro.client import wrap
from repro.core.metrics import latency_summary, throughput_mib_s
from repro.store import CompressedStringStore


def _time_batches(fn, batches) -> list[float]:
    out = []
    for b in batches:
        t0 = time.perf_counter()
        fn(b)
        out.append(time.perf_counter() - t0)
    return out


def store_multiget_bench(size_mib: int, n_queries: int = 20000,
                         batch: int = 1024, seed: int = 0,
                         dataset_name: str = "book_titles") -> list[dict]:
    strings = dataset(dataset_name, size_mib << 20)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, len(strings), n_queries).tolist()
    raw_bytes = sum(len(strings[i]) for i in ids)
    batches = [ids[k : k + batch] for k in range(0, len(ids), batch)]
    rows: list[dict] = []

    def row(variant: str, backend: str, lat_s: list[float], per: str) -> dict:
        total = sum(lat_s)
        lat = latency_summary(lat_s)
        return {
            "dataset": dataset_name, "variant": variant, "backend": backend,
            "n_queries": n_queries, "batch": batch,
            "latency_per": per,
            "p50_us": round(lat["p50_us"], 2),
            "p99_us": round(lat["p99_us"], 2),
            "lookups_per_s": round(n_queries / total, 1),
            "mib_s": round(throughput_mib_s(raw_bytes, total), 2),
            "total_s": round(total, 4),
        }

    for variant16 in (True, False):
        variant = "onpair16" if variant16 else "onpair"
        store = CompressedStringStore.build(
            strings, variant16=variant16, sample_bytes=min(size_mib, 4) << 20,
            seed=seed, cache_bytes=0)
        comp, corpus = store.compressor, store.corpus

        # naive loop: one access() per id (per-call latency samples)
        lat = _time_batches(lambda b: [comp.access(corpus, i) for i in b],
                            [[i] for i in ids])
        rows.append(row(f"{variant}/naive-access", "numpy", lat, "lookup"))

        backends = ["numpy"] + (["jax"] if store.backend == "jax" else [])
        for backend in backends:
            s = CompressedStringStore(comp, corpus, cache_bytes=0,
                                      backend=backend)
            # measured through the v3 session layer (what a caller actually
            # holds); sync multigets ride the client's micro-batching service
            with wrap(s) as client:
                client.multiget(ids[:batch])  # warmup: trigger jit compiles
                lat = _time_batches(client.multiget, batches)
            r = row(f"{variant}/store-multiget", backend, lat, "batch")
            r["jit_shapes"] = [list(x) for x in sorted(s.stats.jit_shapes)]
            rows.append(r)
    return rows


def store_ingest_bench(size_mib: int, seed: int = 0,
                       dataset_name: str = "urls",
                       drift_dataset: str = "book_titles") -> list[dict]:
    """Write-path benchmark: frozen-dictionary append throughput (single and
    Encoder-batched), seal cost amortisation, and a full drift->compact
    cycle (append a different distribution until the monitor trips, then
    time the re-train + rewrite and report the ratio recovery)."""
    from repro.core import registry
    from repro.store.mutable import MutableStringStore

    strings = dataset(dataset_name, size_mib << 20)
    half = len(strings) // 2
    base, incoming = strings[:half], strings[half:]
    art = registry.train("onpair16", base,
                         sample_bytes=min(size_mib, 4) << 20, seed=seed)
    codec = registry.codec_from_artifact(art)  # tables built once, shared
    rows: list[dict] = []

    def build() -> MutableStringStore:
        return MutableStringStore((art, codec), codec.compress(base),
                                  strings_per_segment=4096, cache_bytes=0,
                                  drift_threshold=0.2)

    # single-string appends (per-call parse + tail update), measured through
    # the session layer's write path (client.append -> service -> store)
    store = build()
    one_by_one = incoming[: min(5000, len(incoming))]
    with wrap(store) as client:
        t0 = time.perf_counter()
        for s in one_by_one:
            client.append(s)
        dt = time.perf_counter() - t0
    raw = sum(len(s) for s in one_by_one)
    rows.append({"dataset": dataset_name, "op": "append",
                 "n_strings": len(one_by_one), "total_s": round(dt, 4),
                 "strings_per_s": round(len(one_by_one) / dt, 1),
                 "mib_s": round(throughput_mib_s(raw, dt), 2)})

    # batched appends (one Encoder pass per batch, seals amortised). The
    # collect isolates this phase from the append bench's allocator debris
    # (5000 per-call appends leave enough garbage to cost ~15% here).
    store = build()
    gc.collect()
    with wrap(store) as client:
        t0 = time.perf_counter()
        for k in range(0, len(incoming), 1024):
            client.extend(incoming[k : k + 1024])
        dt = time.perf_counter() - t0
    raw = sum(len(s) for s in incoming)
    rows.append({"dataset": dataset_name, "op": "extend-1024",
                 "n_strings": len(incoming), "total_s": round(dt, 4),
                 "strings_per_s": round(len(incoming) / dt, 1),
                 "mib_s": round(throughput_mib_s(raw, dt), 2),
                 "n_segments": store.segments.n_segments,
                 "tail": store.stats_snapshot()["n_tail_strings"]})

    # pallas-backend encode row, reported alongside the numpy rows but never
    # baseline-gated: it is absent on REPRO_NO_JAX hosts (the CI smoke), and
    # a CPU host runs the kernel interpreted, so n stays small
    try:
        if os.environ.get("REPRO_NO_JAX"):
            raise ImportError("REPRO_NO_JAX is set")
        from repro.kernels.ops import OnPairDevice  # noqa: F401
        have_pallas = True
    except Exception:
        have_pallas = False
    if have_pallas:
        store = MutableStringStore((art, codec), codec.compress(base),
                                   strings_per_segment=4096, cache_bytes=0,
                                   encode_backend="pallas")
        small = incoming[:256]
        t0 = time.perf_counter()
        store.extend(small)
        dt = time.perf_counter() - t0
        raw = sum(len(s) for s in small)
        rows.append({"dataset": dataset_name, "op": "extend-pallas-256",
                     "n_strings": len(small), "total_s": round(dt, 4),
                     "strings_per_s": round(len(small) / dt, 1),
                     "mib_s": round(throughput_mib_s(raw, dt), 2)})

    # drift -> compact cycle: append a different distribution, then rewrite
    drifted = dataset(drift_dataset, min(size_mib, 2) << 20)
    store.extend(drifted)
    snap = store.drift.snapshot()
    report = store.compact()
    rows.append({"dataset": f"{dataset_name}+{drift_dataset}", "op": "compact",
                 "n_strings": report["n_strings"],
                 "total_s": report["total_s"], "train_s": report["train_s"],
                 "strings_per_s": round(report["n_strings"]
                                        / max(report["total_s"], 1e-9), 1),
                 "drift_at_trigger": snap["drift"],
                 "ratio_before": report["ratio_before"],
                 "ratio_after": report["ratio_after"]})
    return rows
