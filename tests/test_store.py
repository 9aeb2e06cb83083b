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


def test_unbounded_onpair_rejects_jax_backend(store_unbounded):
    if not store_unbounded.dictionary.variant16:
        with pytest.raises(ValueError):
            CompressedStringStore(store_unbounded.compressor,
                                  store_unbounded.corpus, backend="jax")


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
