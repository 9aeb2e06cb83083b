"""Tests for the repro.store serving subsystem: byte-for-byte equivalence of
get/multiget/scan against RawCompressor ground truth (OnPair + OnPair16),
routing/bucketing invariants, cache accounting, and the micro-batch service."""

import sys
import threading

import numpy as np
import pytest

from repro.core import RawCompressor, make_onpair, make_onpair16
from repro.data.synth import load_dataset
from repro.store import CompressedStringStore, LRUCache, StoreService

SAMPLE = 1 << 19


@pytest.fixture(scope="module")
def titles():
    # a few hand-placed edge strings, including empties, inside a real corpus
    strings = load_dataset("book_titles", SAMPLE)
    strings[3] = b""
    strings[100] = b""
    strings[7] = b"\x00\xff" * 9
    return strings


@pytest.fixture(scope="module")
def raw_corpus(titles):
    return RawCompressor().compress(titles)


def _build(titles, variant16, **kw):
    comp = (make_onpair16 if variant16 else make_onpair)(sample_bytes=SAMPLE)
    comp.train(titles)
    return CompressedStringStore(comp, comp.compress(titles), **kw)


@pytest.fixture(scope="module")
def store16(titles):
    return _build(titles, True, strings_per_segment=1024)


@pytest.fixture(scope="module")
def store_unbounded(titles):
    return _build(titles, False, strings_per_segment=1024)


# -------------------------------------------------- ground-truth equivalence
@pytest.mark.parametrize("which", ["onpair16", "onpair"])
def test_multiget_matches_raw_ground_truth(titles, raw_corpus, store16,
                                           store_unbounded, which):
    store = store16 if which == "onpair16" else store_unbounded
    raw = RawCompressor()
    rng = np.random.default_rng(42)
    ids = rng.integers(0, len(titles), 1200).tolist()
    got = store.multiget(ids)
    assert got == [raw.access(raw_corpus, i) for i in ids]


@pytest.mark.parametrize("which", ["onpair16", "onpair"])
def test_get_and_scan_match_raw(titles, raw_corpus, store16, store_unbounded,
                                which):
    store = store16 if which == "onpair16" else store_unbounded
    raw = RawCompressor()
    for i in [0, 3, 7, 100, len(titles) - 1]:  # includes empties + binary
        assert store.get(i) == raw.access(raw_corpus, i)
    # scan crossing a segment boundary (segments are 1024 strings wide)
    lo, hi = 1000, 1100
    assert store.scan(lo, hi) == [raw.access(raw_corpus, i)
                                  for i in range(lo, hi)]
    assert store.scan(5, 5) == []


def test_multiget_duplicate_ids_decode_once(store16, titles):
    ids = [9, 9, 12, 9, 3, 12, 3]
    before = store16.stats.decoded_strings
    out = store16.multiget(ids)
    assert out == [titles[i] for i in ids]
    # 3 distinct uncached ids at most -> at most 3 new decoded strings
    assert store16.stats.decoded_strings - before <= 3


def test_out_of_range_ids_raise(store16):
    n = store16.n_strings
    with pytest.raises(IndexError):
        store16.get(n)
    with pytest.raises(IndexError):
        store16.multiget([0, 1, n + 5])
    with pytest.raises(IndexError):
        store16.multiget([-1])
    with pytest.raises(IndexError):
        store16.scan(0, n + 1)


def test_empty_strings_roundtrip_and_cache(titles):
    store = _build(titles, True, cache_bytes=1 << 20)
    assert store.get(3) == b""
    assert store.get(3) == b""          # second hit must come from cache
    assert store.cache.hits >= 1


# ----------------------------------------------------------- batch shaping
def test_bucketing_bounds_jit_shapes(titles):
    """>= 1000 random ids decode through at most 4 static (B, T) shapes."""
    store = _build(titles, True, cache_bytes=0)
    if store.backend != "jax":
        pytest.skip("jax backend unavailable")
    rng = np.random.default_rng(7)
    ids = rng.integers(0, len(titles), 1000).tolist()
    out = store.multiget(ids)
    assert out == [titles[i] for i in ids]
    assert 1 <= len(store.stats.jit_shapes) <= 4
    assert all(B == store.batch_size for B, _ in store.stats.jit_shapes)
    assert len(store.bucket_caps) <= 4
    # every string's token count is covered by the largest bucket
    assert int(store.segments.token_counts().max()) <= int(store.bucket_caps[-1])


def test_numpy_backend_matches_jax_backend(titles, store16):
    comp, corpus = store16.compressor, store16.corpus
    np_store = CompressedStringStore(comp, corpus, backend="numpy",
                                     cache_bytes=0)
    assert np_store.backend == "numpy"
    ids = list(range(0, 600, 3))
    assert np_store.multiget(ids) == store16.multiget(ids)


def test_unbounded_onpair_rejects_jax_backend(store_unbounded, monkeypatch):
    """Unbounded OnPair decodes on the device (chained 16-byte rows): the
    jax backend is refused only where the kernels are unavailable, never
    for the codec."""
    comp, corpus = store_unbounded.compressor, store_unbounded.corpus
    assert not store_unbounded.dictionary.variant16
    if store_unbounded.backend == "jax":
        store = CompressedStringStore(comp, corpus, backend="jax")
        assert store.backend == "jax" and store._device.chained
    monkeypatch.setenv("REPRO_NO_JAX", "1")
    with pytest.raises(ValueError, match="jax not importable"):
        CompressedStringStore(comp, corpus, backend="jax")


# ------------------------------------------------------------------ segments
def test_segment_routing(titles, store16):
    segs = store16.segments
    assert segs.n_segments == -(-len(titles) // 1024)
    for gid in [0, 1023, 1024, len(titles) - 1]:
        seg, local = segs.route(gid)
        assert seg.base_id + local == gid
        np.testing.assert_array_equal(
            seg.string_tokens(local), store16.corpus.string_tokens(gid))
    assert int(segs.token_counts().sum()) == store16.corpus.payload.size // 2
    with pytest.raises(IndexError):
        segs.route(len(titles))


# --------------------------------------------------------------------- cache
def test_lru_cache_eviction_and_accounting():
    c = LRUCache(capacity_bytes=10)
    c.put(1, b"aaaa")
    c.put(2, b"bbbb")
    assert c.get(1) == b"aaaa"          # 1 is now most-recent
    c.put(3, b"cccc")                   # 12 bytes > 10: evicts LRU (2)
    assert c.get(2) is None
    assert c.get(1) == b"aaaa"
    assert c.evictions == 1
    assert c.current_bytes <= 10
    c.put(1, b"x")                      # overwrite adjusts accounting
    assert c.current_bytes == len(b"x") + len(b"cccc")
    assert c.get(4) is None
    st = c.stats()
    assert st["hits"] == 2 and st["misses"] == 2

    disabled = LRUCache(capacity_bytes=0)
    disabled.put(1, b"zz")
    assert disabled.get(1) is None

    # an entry larger than the whole budget must be rejected, not admitted
    c2 = LRUCache(capacity_bytes=10)
    c2.put(1, b"aaaa")
    c2.put(2, b"x" * 100)
    assert c2.get(2) is None and c2.get(1) == b"aaaa"
    assert c2.current_bytes <= 10


def test_cache_stores_empty_strings():
    c = LRUCache(capacity_bytes=100)
    c.put(5, b"")
    assert c.get(5) == b""
    assert c.hits == 1 and c.misses == 0


class _ReferenceLRU:
    """The LRU rule spelled out on a list ordered oldest first."""

    def __init__(self, capacity_bytes):
        self.capacity_bytes = capacity_bytes
        self.items: list[tuple[int, bytes]] = []
        self.hits = self.misses = self.evictions = 0

    @property
    def current_bytes(self):
        return sum(len(v) for _, v in self.items)

    def _pop(self, key):
        for n, (k, _) in enumerate(self.items):
            if k == key:
                return self.items.pop(n)
        return None

    def get(self, key):
        item = self._pop(key)
        if item is None:
            self.misses += 1
            return None
        self.items.append(item)
        self.hits += 1
        return item[1]

    def put(self, key, value):
        if self.capacity_bytes <= 0 or len(value) > self.capacity_bytes:
            return
        self._pop(key)
        self.items.append((key, value))
        while self.current_bytes > self.capacity_bytes and len(self.items) > 1:
            self.items.pop(0)
            self.evictions += 1

    def clear(self):
        self.items.clear()


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("capacity", [0, 3, 10, 64, 1000])
def test_lru_cache_matches_reference_model(capacity, seed):
    """Seeded get/put/clear sequences leave the cache and the list model in
    the same state after every step: values, order, bytes and counters.
    Value lengths run 0-16, so a budget of 3 refuses most values and b""
    is common."""
    rng = np.random.default_rng(seed)
    c, ref = LRUCache(capacity_bytes=capacity), _ReferenceLRU(capacity)
    for _ in range(3000):
        op, key = rng.random(), int(rng.integers(0, 40))
        if op < 0.45:
            assert c.get(key) == ref.get(key)
        elif op < 0.995:
            n = 0 if rng.random() < 0.15 else int(rng.integers(1, 17))
            value = bytes([key % 256]) * n
            c.put(key, value)
            ref.put(key, value)
        else:
            c.clear()
            ref.clear()
        assert list(c._data.items()) == ref.items
        assert len(c) == len(ref.items)
        assert c.current_bytes == ref.current_bytes <= max(capacity, 0)
        assert (c.hits, c.misses, c.evictions) == \
            (ref.hits, ref.misses, ref.evictions)
    st = c.stats()
    assert (st["entries"], st["bytes"], st["hits"], st["misses"],
            st["evictions"]) == (len(ref.items), ref.current_bytes, ref.hits,
                                 ref.misses, ref.evictions)


def test_lru_cache_churn_evicts_oldest_first():
    """250k puts of fresh keys through a 4 KiB budget, with every 101st
    step a hit on the oldest entry: the budget holds throughout, and the
    cache always holds the most recent keys in recency order."""
    lens = np.random.default_rng(11).integers(0, 33, 250_000).tolist()
    cap = 4096
    c = LRUCache(capacity_bytes=cap)
    order: list[int] = []  # live keys, oldest first
    for key, n in enumerate(lens):
        if key % 101 == 100:
            oldest = order.pop(0)
            assert c.get(oldest) == b"v" * lens[oldest]
            order.append(oldest)
        c.put(key, b"v" * n)
        order.append(key)
        del order[:-len(c)]
        assert c.current_bytes <= cap
        if key % 997 == 0:
            assert list(c._data) == order
    assert list(c._data) == order
    assert c.current_bytes == sum(lens[k] for k in order)
    assert c.evictions == len(lens) - len(c)
    assert c.hits == len(lens) // 101 and c.misses == 0


# ------------------------------------------------------------------- service
def test_service_coalesces_and_matches(titles, store16):
    with StoreService(store16, max_batch=64, max_wait_s=0.002) as svc:
        rng = np.random.default_rng(3)
        ids = rng.integers(0, len(titles), 300).tolist()
        errs: list[Exception] = []

        def client(chunk):
            try:
                for i in chunk:
                    assert svc.get(int(i)) == titles[int(i)]
            except Exception as e:  # surfaced after join
                errs.append(e)

        threads = [threading.Thread(target=client, args=(ids[k::4],))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        st = svc.stats()
        assert st["requests"] == 300
        assert st["batches"] <= 300     # some coalescing happened is typical;
        bad = svc.submit(len(titles) + 1)
        with pytest.raises(IndexError):
            bad.result(timeout=5)
    with pytest.raises(RuntimeError):
        svc.get(0)                      # closed service fails fast


# ----------------------------------------------------- satellite: access()
@pytest.mark.parametrize("variant16", [True, False])
def test_access_equals_decompress_all_slice(titles, variant16):
    comp = (make_onpair16 if variant16 else make_onpair)(sample_bytes=SAMPLE)
    comp.train(titles)
    corpus = comp.compress(titles[:500])
    blob = comp.decompress_all(corpus)
    # per-string boundaries derived from the token streams alone
    lens = comp.dictionary.lens
    starts = np.zeros(corpus.n_strings + 1, dtype=np.int64)
    for i in range(corpus.n_strings):
        toks = np.asarray(corpus.string_tokens(i), dtype=np.int64)
        starts[i + 1] = starts[i] + int(lens[toks].sum())
    assert starts[-1] == len(blob)
    for i in range(corpus.n_strings):
        assert comp.access(corpus, i) == blob[starts[i] : starts[i + 1]]


def test_stats_snapshot_shape(store16):
    snap = store16.stats_snapshot()
    for key in ("lookups", "batches", "jit_shapes", "multiget_latency",
                "cache", "backend", "bucket_caps", "memory_bytes"):
        assert key in snap
    assert snap["multiget_latency"]["count"] >= 1
    assert 0.0 <= snap["cache"]["hit_rate"] <= 1.0
    # memory accounting includes the decode matrix + LPM tables
    assert store16.dictionary.resident_bytes > store16.dictionary.total_bytes
    assert snap["memory_bytes"] >= store16.dictionary.resident_bytes


# ------------------------------------------------ no quiet numpy fallback
class _KernelModule:
    """Stands in for repro.kernels.ops: importing OnPairDevice raises."""

    def __init__(self, exc):
        self._exc = exc

    def __getattr__(self, name):
        raise self._exc


def test_broken_kernel_import_fails_store_loudly(titles, monkeypatch):
    """A kernel import that fails for any reason but a missing jax (here: a
    libtpu that cannot start) must not resolve backend='auto' to numpy."""
    comp = make_onpair16(sample_bytes=SAMPLE)
    comp.train(titles[:2000])
    corpus = comp.compress(titles[:2000])
    monkeypatch.setitem(sys.modules, "repro.kernels.ops", _KernelModule(
        RuntimeError("TPU backend failed to initialise")))
    with pytest.raises(RuntimeError, match="failed to initialise"):
        CompressedStringStore(comp, corpus)
    # the numpy backend never asks for the kernels
    assert CompressedStringStore(comp, corpus, backend="numpy").backend == \
        "numpy"
    # a host without jax is the one case that resolves to numpy
    monkeypatch.setitem(sys.modules, "repro.kernels.ops", _KernelModule(
        ModuleNotFoundError("No module named 'jax'", name="jax")))
    assert CompressedStringStore(comp, corpus).backend == "numpy"


def test_stats_name_backend_and_device(store16):
    snap = store16.stats_snapshot()
    if snap["backend"] == "jax":
        import jax
        dev = jax.devices()[0]
        assert snap["device"] == {"platform": dev.platform,
                                  "kind": dev.device_kind, "id": dev.id}
    else:
        assert "device" not in snap


def test_copy_counters_count_what_each_decode_path_copies(titles, store16):
    """The store counts the tokens that reach a decode, the strings each
    path decodes and, on the device path, the bytes of every array a batch
    copies in and reads back; the numpy path copies nothing."""
    comp, corpus = store16.compressor, store16.corpus
    ids = list(range(1000, 1700, 3))
    tokens = sum(int(store16.segments.string_tokens(i).size) for i in ids)
    np_store = CompressedStringStore(comp, corpus, backend="numpy",
                                     cache_bytes=0)
    assert np_store.multiget(ids) == [titles[i] for i in ids]
    snap = np_store.stats_snapshot()
    assert (snap["real_tokens"], snap["host_strings"], snap["device_strings"],
            snap["h2d_bytes"], snap["d2h_bytes"]) == (tokens, len(ids), 0, 0, 0)
    for gone in ("decode_seconds", "decode_mib_s", "lookups_per_s"):
        assert gone not in snap
    if store16.backend != "jax":
        pytest.skip("jax backend unavailable")
    import jax.numpy as jnp

    from repro.kernels import onpair_decode
    from repro.kernels.ops import pack_token_matrix

    store = CompressedStringStore(comp, corpus, cache_bytes=0)
    assert store.multiget(ids) == [titles[i] for i in ids]
    snap = store.stats_snapshot()
    assert (snap["real_tokens"], snap["device_strings"],
            snap["host_strings"]) == (tokens, len(ids), 0)
    # int32 tokens [B, T] and counts [B] go in, per batch of each shape
    shapes = snap["batches_by_shape"]
    assert sum(shapes.values()) == snap["batches"]
    assert snap["h2d_bytes"] == sum(
        n * (b * t * 4 + b * 4) for b, t in
        (map(int, k.split("x")) for k in shapes) for n in [shapes[f"{b}x{t}"]])
    dev = store._device
    assert (snap["h2d_bytes"], snap["d2h_bytes"]) == (dev.h2d_bytes,
                                                      dev.d2h_bytes)
    # bytes come back, one per decoded byte of the [B, 16 T] output: a
    # quarter of the int32 words the kernel writes; no lengths
    assert snap["d2h_bytes"] == sum(
        n * b * 16 * t for b, t in
        (map(int, k.split("x")) for k in shapes) for n in [shapes[f"{b}x{t}"]])
    # one batch by hand: the counts are the nbytes of the arrays copied
    tok, n_tok = pack_token_matrix(
        [store16.segments.string_tokens(i) for i in ids[:5]], pad_batch=8)
    h0, d0 = dev.h2d_bytes, dev.d2h_bytes
    dev.decode_batch(tok, n_tok, 16 * tok.shape[1])
    out, olen = onpair_decode.decode_compact(
        jnp.asarray(tok), jnp.asarray(n_tok), dev.dd.mat16, dev.dd.lens,
        16 * tok.shape[1])
    assert out.dtype == np.uint8
    assert dev.h2d_bytes - h0 == tok.nbytes + n_tok.nbytes
    assert dev.d2h_bytes - d0 == np.asarray(out).nbytes == 8 * 16 * tok.shape[1]
