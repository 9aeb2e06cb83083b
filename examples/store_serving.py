"""Example: serving point lookups from the compressed string store.

1. Train OnPair16 and open a CompressedStringStore over the corpus
   (compressed payload + segments + LRU cache + Pallas batch decoder).
2. Batched multiget — note the bounded set of jit-compiled decode shapes.
3. Range scan — one vectorised decode per touched segment.
4. StoreService — concurrent clients coalesced into micro-batches.
5. Persistence + the v3 client layer — store.save(dir), then
   connect("file://<dir>"): the train-once dictionary artifact + corpus
   reopen with no retraining behind the uniform session surface
   (sync + async + streaming scan + one stats schema).

  PYTHONPATH=src python examples/store_serving.py
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import tempfile
import threading
import time

import numpy as np

from repro.data.synth import load_dataset
from repro.store import CompressedStringStore, StoreService

strings = load_dataset("urls", 2 << 20)
store = CompressedStringStore.build(strings, sample_bytes=2 << 20,
                                    strings_per_segment=4096)
print(f"store: {len(store)} strings, {store.segments.n_segments} segments, "
      f"{store.backend} backend, bucket caps {[int(c) for c in store.bucket_caps]}, "
      f"{store.memory_bytes / (1 << 20):.2f} MiB resident")

# --- batched point lookups (the paper's random-access workload, batched) ----
rng = np.random.default_rng(0)
ids = rng.integers(0, len(store), 2000).tolist()
t0 = time.perf_counter()
out = store.multiget(ids)
dt = time.perf_counter() - t0
assert out == [strings[i] for i in ids]
print(f"multiget: {len(ids)} lookups in {dt * 1e3:.1f} ms "
      f"({len(ids) / dt:.0f} lookups/s), "
      f"jit decode shapes: {sorted(store.stats.jit_shapes)}")

# --- range scan -------------------------------------------------------------
t0 = time.perf_counter()
docs = store.scan(1000, 3000)
assert docs == strings[1000:3000]
print(f"scan[1000:3000): {len(docs)} strings in "
      f"{(time.perf_counter() - t0) * 1e3:.1f} ms")

# --- micro-batching service: concurrent clients, coalesced decodes ----------
with StoreService(store, max_batch=256, max_wait_s=0.002) as svc:
    def client(seed: int) -> None:
        r = np.random.default_rng(seed)
        for i in r.integers(0, len(store), 200):
            assert svc.get(int(i)) == strings[int(i)]

    threads = [threading.Thread(target=client, args=(s,)) for s in range(4)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    st = svc.stats()
    print(f"service: {st['requests']} requests from 4 clients in "
          f"{dt * 1e3:.0f} ms, {st['batches']} batches "
          f"(avg {st['avg_batch']} lookups/batch), "
          f"p99 {st['request_latency']['p99_us']:.0f} us")

snap = store.stats_snapshot()
print(f"totals: {snap['lookups']} lookups, cache hit rate "
      f"{snap['cache']['hit_rate']:.2f}, {snap['decoded_strings']} strings "
      f"decoded ({snap['device_strings']} by the kernel), "
      f"{snap['h2d_bytes']} B copied in, {snap['d2h_bytes']} B read back")

# --- persistence + Client API v3: one session over the saved store ----------
from repro.client import connect

with tempfile.TemporaryDirectory() as d:
    t0 = time.perf_counter()
    store.save(d)
    save_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    with connect(f"file://{d}") as client:         # mmap, no retraining
        open_ms = (time.perf_counter() - t0) * 1e3
        assert client.multiget(ids[:200]) == store.multiget(ids[:200])
        # async pipelining: several batched lookups in flight at once, all
        # coalesced through the session's micro-batching service
        futs = [client.multiget_async(ids[k : k + 100])
                for k in range(0, 1000, 100)]
        assert [b for f in futs for b in f.result(30)] == \
            store.multiget(ids[:1000])
        # streamed range decode (never materialises the whole range)
        assert list(client.scan_iter(1000, 3000, chunk=512)) == docs
        snap = client.stats()
        print(f"client: saved in {save_ms:.1f} ms, connect('file://...') in "
              f"{open_ms:.1f} ms ({client.backend.artifact.num_entries} dict "
              f"entries); {snap['ops']} -> p99 "
              f"{snap['latency_summary']['p99_us']:.0f} us, "
              f"{snap['throughput_mib_s']} MiB/s, multiget identical")
