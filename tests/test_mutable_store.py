"""Writable-store tests: frozen-dictionary append (tail + sealing), tail-aware
scan/stats, save→open round-trips of unsealed tails, drift-triggered
compaction byte-identity, cache invalidation, service read/append
interleaving, and sharded append/compact routing. Everything runs on a
numpy-only host; the jax path is exercised implicitly when available."""

import json
import os
import threading

import numpy as np
import pytest

from repro.core import registry
from repro.core.codec import Encoder
from repro.data.synth import load_dataset
from repro.distributed import ShardedStringStore, save_sharded
from repro.store import (CompressedStringStore, DriftMonitor,
                         MutableStringStore, StoreService)
from repro.store.drift import segment_ratio, segment_report

SAMPLE = 1 << 18
SPS = 256  # small segments so appends cross seal boundaries quickly


@pytest.fixture(scope="module")
def titles():
    strings = load_dataset("book_titles", SAMPLE)
    strings[3] = b""
    strings[7] = b"\x00\xff" * 9
    return strings


@pytest.fixture(scope="module")
def artifact(titles):
    return registry.train("onpair16", titles, sample_bytes=SAMPLE)


def _junk(n: int, length: int = 48, seed: int = 0) -> list:
    """Incompressible strings — a drifted distribution for any dictionary."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, length, dtype=np.uint8).tobytes()
            for _ in range(n)]


def _mutable(artifact, strings, **kw):
    corpus = Encoder(artifact).encode(strings) if strings else None
    kw.setdefault("strings_per_segment", SPS)
    kw.setdefault("cache_bytes", 1 << 20)
    return MutableStringStore(artifact, corpus, **kw)


# ------------------------------------------------- append == from-scratch
def test_append_matches_from_scratch_build(titles, artifact):
    base, extra = titles[:700], titles[700:1300]
    store = _mutable(artifact, base)
    ids = store.extend(extra)
    assert ids == list(range(700, 1300))
    assert store.n_strings == 1300

    # ground truth: the same 1300 strings encoded in one immutable pass
    scratch = CompressedStringStore(
        artifact, Encoder(artifact).encode(base + extra),
        strings_per_segment=SPS)
    rng = np.random.default_rng(0)
    some = rng.integers(0, 1300, 500).tolist()
    assert store.multiget(some) == scratch.multiget(some)
    for i in (0, 3, 7, 699, 700, 1299):
        assert store.get(i) == scratch.get(i)
    assert store.scan(0, 1300) == scratch.scan(0, 1300)


def test_appended_ids_are_contiguous_and_empty_ok(artifact, titles):
    store = _mutable(artifact, titles[:10])
    assert store.extend([]) == []
    a = store.append(b"")
    b = store.append(b"x" * 100)
    assert (a, b) == (10, 11)
    assert store.get(a) == b"" and store.get(b) == b"x" * 100


def test_store_can_start_empty(artifact, titles):
    store = _mutable(artifact, [])
    assert store.n_strings == 0
    assert store.scan(0, 0) == []
    ids = store.extend(titles[:SPS + 5])
    assert ids[0] == 0 and store.n_strings == SPS + 5
    assert store.scan(0, SPS + 5) == titles[:SPS + 5]
    store.seal_barrier()                   # let the background seal land
    assert store.segments.n_segments == 1  # one sealed + 5 in tail


# --------------------------------------------------------- seal boundaries
def test_seal_boundary_exactly_full_tail(artifact, titles):
    base = titles[:SPS]  # base corpus = exactly one full segment
    store = _mutable(artifact, base)
    n_seg0 = store.segments.n_segments
    store.extend(titles[SPS : 2 * SPS])           # exactly fills one tail
    store.seal_barrier()
    snap = store.stats_snapshot()
    assert snap["n_tail_strings"] == 0            # sealed, nothing left over
    assert store.segments.n_segments == n_seg0 + 1
    assert snap["n_sealed_strings"] == 2 * SPS
    assert store.scan(0, 2 * SPS) == titles[: 2 * SPS]


def test_seal_boundary_empty_tail_seal_is_noop(artifact, titles):
    store = _mutable(artifact, titles[:20])
    n_seg = store.segments.n_segments
    store.seal()                                   # empty tail: nothing to do
    assert store.segments.n_segments == n_seg
    store.append(b"tailed")
    store.seal()                                   # force-seal a short tail
    assert store.segments.n_segments == n_seg + 1
    assert store.stats_snapshot()["n_tail_strings"] == 0
    assert store.get(20) == b"tailed"


def test_seal_with_partial_base_segment(artifact, titles):
    # base corpus ends mid-segment: appended seals land behind a short
    # segment, so routing must bisect, not divide
    base = titles[: SPS + 37]
    store = _mutable(artifact, base)
    store.extend(titles[SPS + 37 : 3 * SPS])
    assert store.scan(0, 3 * SPS) == titles[: 3 * SPS]
    for gid in (SPS + 36, SPS + 37, 2 * SPS, 3 * SPS - 1):
        assert store.get(gid) == titles[gid]


# -------------------------------------- satellite: tail-aware scan + stats
def test_scan_straddles_sealed_tail_boundary(artifact, titles):
    store = _mutable(artifact, titles[:300])      # seg of 256 + 44 sealed? no:
    # 300 base strings => segments [256, 44]; appends go to the tail
    store.extend(titles[300:350])                 # 50 unsealed tail strings
    snap = store.stats_snapshot()
    assert snap["n_sealed_strings"] == 300 and snap["n_tail_strings"] == 50
    assert snap["n_strings"] == 350
    # ranges fully sealed / straddling / fully tail
    assert store.scan(250, 300) == titles[250:300]
    assert store.scan(280, 340) == titles[280:340]
    assert store.scan(300, 350) == titles[300:350]
    assert store.scan(349, 350) == titles[349:350]
    assert store.scan(350, 350) == []
    with pytest.raises(IndexError):
        store.scan(0, 351)
    # multiget across the boundary, same decode answers
    ids = [0, 299, 300, 349]
    assert store.multiget(ids) == [titles[i] for i in ids]


def test_stats_snapshot_tail_aware(artifact, titles):
    store = _mutable(artifact, titles[:100])
    store.extend(titles[100:120])
    snap = store.stats_snapshot()
    for key in ("n_sealed_strings", "n_tail_strings", "drift", "compactions",
                "version"):
        assert key in snap
    assert snap["n_strings"] == 120
    assert snap["memory_bytes"] >= store._tail_payload_bytes() > 0


# ------------------------------------------------------- save/open roundtrip
def test_save_open_roundtrip_with_unsealed_tail(artifact, titles, tmp_path):
    store = _mutable(artifact, titles[:400])
    store.extend(titles[400:500])                 # leaves an unsealed tail
    assert store.stats_snapshot()["n_tail_strings"] > 0
    d = str(tmp_path / "wstore")
    store.save(d)
    assert os.path.exists(os.path.join(d, "current.json"))
    assert os.path.isdir(os.path.join(d, "v0000"))

    re = MutableStringStore.open(d)
    assert re.n_strings == 500
    assert re.stats_snapshot()["n_tail_strings"] == \
        store.stats_snapshot()["n_tail_strings"]
    assert re.scan(0, 500) == titles[:500]
    # drift window survives the round-trip
    assert re.drift.raw_bytes == store.drift.raw_bytes
    assert re.drift.baseline_ratio == pytest.approx(store.drift.baseline_ratio)
    # and the reopened store keeps appending / sealing on the same boundaries
    ids = re.extend(titles[500:600])
    assert ids == list(range(500, 600))
    assert re.scan(450, 600) == titles[450:600]


def test_open_plain_readonly_store_dir_as_writable(titles, tmp_path):
    flat = CompressedStringStore.build(titles[:300], sample_bytes=SAMPLE,
                                       strings_per_segment=SPS)
    d = str(tmp_path / "flat")
    flat.save(d)
    store = MutableStringStore.open(d)
    assert store.n_strings == 300
    store.append(b"appended onto a read-only layout")
    assert store.get(300) == b"appended onto a read-only layout"


# --------------------------------------------------------------- compaction
def test_compact_byte_identity_and_versioned_swap(artifact, titles, tmp_path):
    store = _mutable(artifact, titles[:600])
    store.extend(titles[600:700])
    store.extend(_junk(400))                      # inject drift
    assert store.drift.should_compact()
    live_before = store.scan(0, store.n_strings)

    d = str(tmp_path / "cstore")
    store.save(d)
    report = store.compact()
    assert report["version"] == "v0001"
    assert report["ratio_after"] >= report["ratio_before"]
    assert store.compactions == 1
    # all live strings byte-identical through every read path
    n = store.n_strings
    assert store.scan(0, n) == live_before
    rng = np.random.default_rng(1)
    ids = rng.integers(0, n, 300).tolist()
    assert store.multiget(ids) == [live_before[i] for i in ids]
    # drift window restarted against the new dictionary
    assert store.drift.observations == 0 and store.drift.drift == 0.0
    # versioned directory swapped atomically, old generation pruned
    assert sorted(os.listdir(d)) == ["current.json", "v0001"]
    re = MutableStringStore.open(d)
    assert re.version_id == 1
    assert re.scan(0, n) == live_before


def test_compact_drops_cached_entries_for_rewritten_segments(artifact, titles):
    store = _mutable(artifact, titles[:300], cache_bytes=1 << 20)
    store.multiget(list(range(50)))
    store.get(0)
    assert store.cache.hits >= 1 and len(store.cache) > 0
    store.compact()
    assert len(store.cache) == 0                  # rewritten segments dropped
    assert store.cache.current_bytes == 0
    assert store.get(0) == titles[0]              # decoded fresh, still right


def test_compact_on_empty_store_is_noop(artifact):
    store = _mutable(artifact, [])
    report = store.compact()
    assert report["n_strings"] == 0 and store.n_strings == 0


def test_auto_compact_triggers_on_drift(artifact, titles):
    store = _mutable(artifact, titles[:300], auto_compact=True,
                     drift_threshold=0.5)
    store.extend(_junk(600))
    assert store.compactions >= 1                 # tripped during extend
    assert store.drift.observations == 0          # window restarted
    assert store.get(300 + 599) == store.scan(0, store.n_strings)[-1]


# ------------------------------------------------------------ drift monitor
def test_drift_monitor_math():
    m = DriftMonitor(threshold=0.2, baseline_ratio=2.0, min_bytes=100)
    assert m.drift == 0.0 and not m.should_compact()
    m.observe(200, 100)                           # ratio 2.0: no drift
    assert m.drift == pytest.approx(0.0)
    m.observe(200, 300)                           # now 400/400 = 1.0
    assert m.drift == pytest.approx(0.5)
    assert m.should_compact()
    m.reset(3.0)
    assert m.observations == 0 and m.baseline_ratio == 3.0
    assert m.drift == 0.0


def test_drift_monitor_min_bytes_floor_and_validation():
    m = DriftMonitor(threshold=0.2, baseline_ratio=4.0, min_bytes=1 << 20)
    m.observe(100, 100)                           # terrible ratio, tiny data
    assert m.drift > 0.2 and not m.should_compact()
    with pytest.raises(ValueError):
        DriftMonitor(threshold=1.5)
    m2 = DriftMonitor(threshold=0.2)              # no baseline: never drifts
    m2.observe(10, 1000)
    assert m2.drift == 0.0 and not m2.should_compact()


def test_empty_started_store_seeds_baseline_and_detects_drift(artifact,
                                                              titles):
    # a store populated purely by appends has no train-time ratio: the first
    # observation window seeds the baseline so drift detection still works
    store = _mutable(artifact, [], drift_threshold=0.3)
    store.extend(titles[:800])                    # compressible seed window
    assert store.drift.baseline_ratio is not None
    assert not store.drift.should_compact()
    store.extend(_junk(600))                      # distribution shift
    assert store.drift.should_compact()


def test_segment_ratio_report(artifact, titles):
    store = _mutable(artifact, titles[:600])
    rows = segment_report(store)
    assert len(rows) == store.segments.n_segments
    for seg, row in zip(store.segments.segments, rows):
        r = segment_ratio(store.dictionary, seg)
        assert r == pytest.approx(row["ratio"], abs=1e-3)
        assert r > 1.0                            # trained data compresses
        assert row["n_strings"] == seg.n_strings


# ------------------------------------------- service: reads + appends mixed
def test_service_interleaved_reads_and_appends(artifact, titles):
    base = titles[:400]
    store = _mutable(artifact, base)
    appended = titles[400:600]
    seen_n = []
    errs: list = []

    with StoreService(store, max_batch=64, max_wait_s=0.002) as svc:
        def writer():
            try:
                futs = [svc.submit_append(s) for s in appended]
                ids = [f.result(30) for f in futs]
                # service folds appends into ordered extend() batches: ids
                # come back contiguous from 400
                assert sorted(ids) == list(range(400, 600))
                assert ids == sorted(ids)
            except Exception as e:
                errs.append(e)

        def reader(seed):
            try:
                rng = np.random.default_rng(seed)
                last_n = 0
                for _ in range(150):
                    n = store.n_strings
                    assert n >= last_n            # monotonic growth
                    last_n = n
                    seen_n.append(n)
                    i = int(rng.integers(0, 400))  # stable prefix
                    assert svc.get(i, timeout=30) == base[i]
            except Exception as e:
                errs.append(e)

        threads = [threading.Thread(target=writer)] + \
                  [threading.Thread(target=reader, args=(s,)) for s in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs[0]
        st = svc.stats()
        assert st["appends"] == 200
        assert st["append_batches"] <= st["appends"]

    # after the dust settles: every appended string is byte-identical
    assert store.n_strings == 600
    assert store.scan(0, 600) == titles[:600]


def test_service_append_to_readonly_store_fails(titles):
    store = CompressedStringStore.build(titles[:50], sample_bytes=SAMPLE)
    with StoreService(store) as svc:
        with pytest.raises(TypeError):
            svc.submit_append(b"nope").result(5)


# ------------------------------------------------------- sharded write path
def test_sharded_append_and_compact_route_to_owning_shard(titles, tmp_path):
    store = CompressedStringStore.build(titles[:512], sample_bytes=SAMPLE,
                                        strings_per_segment=128)
    d = str(tmp_path / "shards")
    save_sharded(store, d, 2)
    sharded = ShardedStringStore.open(d, writable=True)
    n0 = sharded.n_strings
    gid = sharded.append(b"routed to the last shard")
    assert gid == n0
    assert sharded.get(gid) == b"routed to the last shard"
    assert sharded.bounds[-1][1] == n0 + 1
    # only the owning (last) shard grew
    assert sharded.stores[-1].n_strings == n0 - sharded.bounds[-1][0] + 1
    ids = sharded.extend(_junk(300))
    assert ids == list(range(n0 + 1, n0 + 301))
    live = [sharded.get(i) for i in range(sharded.n_strings)]
    reports = sharded.compact(shard=len(sharded.stores) - 1)
    assert len(reports) == 1
    assert [sharded.get(i) for i in range(sharded.n_strings)] == live


def test_sharded_concurrent_extends_stay_monotonic(titles, tmp_path):
    store = CompressedStringStore.build(titles[:256], sample_bytes=SAMPLE,
                                        strings_per_segment=128)
    d = str(tmp_path / "race-shards")
    save_sharded(store, d, 2)
    sharded = ShardedStringStore.open(d, writable=True)
    results: dict[int, list[int]] = {}
    errs: list = []

    def writer(k):
        try:
            results[k] = sharded.extend(
                [b"w%d-%d" % (k, i) for i in range(50)])
        except Exception as e:
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[0]
    assert sharded.n_strings == 256 + 200         # no lost updates
    for k, ids in results.items():                # every acknowledged id reads
        assert sharded.multiget(ids) == [b"w%d-%d" % (k, i)
                                         for i in range(50)]


def test_sharded_readonly_append_raises(titles, tmp_path):
    store = CompressedStringStore.build(titles[:256], sample_bytes=SAMPLE,
                                        strings_per_segment=128)
    d = str(tmp_path / "ro-shards")
    save_sharded(store, d, 2)
    sharded = ShardedStringStore.open(d)
    with pytest.raises(TypeError):
        sharded.append(b"x")
    with pytest.raises(TypeError):
        sharded.compact()


# ------------------------------------------------ review-fix regressions
def test_memory_bytes_stable_across_seal(artifact, titles):
    # sealed-from-tail segments must stay in the resident accounting
    store = _mutable(artifact, titles[:100], cache_bytes=0)
    store.append(titles[100])
    before = store.memory_bytes
    assert store.stats_snapshot()["n_tail_strings"] == 1
    store.seal()                                  # tail -> segment
    assert store.memory_bytes >= before           # nothing vanished

    store2 = _mutable(artifact, titles[:SPS], cache_bytes=0)
    store2.extend(titles[SPS : 2 * SPS])          # seals a full segment
    store2.seal_barrier()
    seg_bytes = sum(s.payload_bytes + s.offsets.nbytes
                    for s in store2.segments.segments)
    assert store2.memory_bytes >= seg_bytes


def test_drift_threshold_survives_save_open(artifact, titles, tmp_path):
    store = _mutable(artifact, titles[:50], drift_threshold=0.05)
    d = str(tmp_path / "thresh")
    store.save(d)
    re = MutableStringStore.open(d)
    assert re.drift.threshold == pytest.approx(0.05)
    # explicit overrides beat the saved params (and must not TypeError)
    re2 = MutableStringStore.open(d, drift_threshold=0.4, train_ratio=9.0)
    assert re2.drift.threshold == pytest.approx(0.4)
    assert re2.drift.baseline_ratio == pytest.approx(9.0)


def test_readonly_open_follows_versioned_layout(artifact, titles, tmp_path):
    store = _mutable(artifact, titles[:300])
    store.extend(titles[300:320])
    d = str(tmp_path / "verdir")
    store.save(d)
    ro = CompressedStringStore.open(d)            # read-only, same generation
    assert ro.n_strings == 320
    assert ro.scan(0, 320) == titles[:320]


def test_flat_dir_upgrade_leaves_no_stale_generation(titles, tmp_path):
    flat = CompressedStringStore.build(titles[:100], sample_bytes=SAMPLE,
                                       strings_per_segment=SPS)
    d = str(tmp_path / "upgrade")
    flat.save(d)
    m = MutableStringStore.open(d)
    m.append(b"appended then compacted")
    m.compact()                                   # upgrades d to versioned
    assert not os.path.exists(os.path.join(d, "corpus.rpc"))
    assert not os.path.exists(os.path.join(d, "dictionary.rpa"))
    # BOTH open paths now agree on the same generation
    assert CompressedStringStore.open(d).n_strings == 101
    assert MutableStringStore.open(d).get(100) == b"appended then compacted"


def test_sharded_appends_persist_across_save_open(titles, tmp_path):
    store = CompressedStringStore.build(titles[:256], sample_bytes=SAMPLE,
                                        strings_per_segment=128)
    d = str(tmp_path / "durable-shards")
    save_sharded(store, d, 2)
    sharded = ShardedStringStore.open(d, writable=True)
    ids = sharded.extend([b"persisted-one", b"persisted-two"])
    sharded.save()
    # only the dirty (appended-to) shard was rewritten to a versioned
    # layout; the untouched shard keeps the shared flat layout
    assert not os.path.exists(os.path.join(d, "shard-0000", "current.json"))
    assert os.path.exists(os.path.join(d, "shard-0001", "current.json"))
    re = ShardedStringStore.open(d, writable=True)
    assert re.n_strings == 258
    assert [re.get(i) for i in ids] == [b"persisted-one", b"persisted-two"]
    assert re.multiget(list(range(256))) == titles[:256]
    # a read-only reopen of the same layout serves the saved appends but
    # rejects writes — writable=False must hold for versioned shards too
    ro = ShardedStringStore.open(d)
    assert [ro.get(i) for i in ids] == [b"persisted-one", b"persisted-two"]
    with pytest.raises(TypeError):
        ro.extend([b"nope"])
    # save() is in-place only: a router not opened from disk has no target
    with pytest.raises(ValueError):
        ShardedStringStore(re.stores, re.bounds).save()


def test_sharded_open_rejects_out_of_band_nontail_growth(titles, tmp_path):
    from repro.distributed.shard_store import open_shard
    store = CompressedStringStore.build(titles[:256], sample_bytes=SAMPLE,
                                        strings_per_segment=128)
    d = str(tmp_path / "oob-shards")
    save_sharded(store, d, 2)
    # grow a NON-tail shard behind the router's back and persist it
    shard0 = open_shard(d, 0, writable=True)
    shard0.append(b"smuggled in")
    shard0.save(os.path.join(d, "shard-0000"))
    with pytest.raises(ValueError, match="only the last shard may grow"):
        ShardedStringStore.open(d)
    # the tail shard growing out of band is fine: its bound extends
    d2 = str(tmp_path / "tail-shards")
    save_sharded(store, d2, 2)
    tail = open_shard(d2, 1, writable=True)
    tail.append(b"tail growth ok")
    tail.save(os.path.join(d2, "shard-0001"))
    re = ShardedStringStore.open(d2)
    assert re.n_strings == 257
    assert re.get(256) == b"tail growth ok"


def test_save_sharded_covers_appended_strings(artifact, titles, tmp_path):
    # sharding a writable store must snapshot sealed-tail segments + tail,
    # not the stale construction-time corpus
    store = _mutable(artifact, titles[:300])
    store.extend(titles[300:500])                 # seals one segment + tail
    d = str(tmp_path / "append-shards")
    bounds = save_sharded(store, d, 2)
    assert bounds[-1][1] == 500
    sharded = ShardedStringStore.open(d)
    assert sharded.n_strings == 500
    assert sharded.multiget(list(range(500))) == titles[:500]


def test_swap_state_never_unpublishes_ids(artifact, titles):
    # lock-free n_strings readers rely on the published count never dipping,
    # even while compact() swaps in a corpus that excludes the delta
    store = _mutable(artifact, titles[:100])
    new_comp = registry.codec_from_artifact(store.artifact)
    new_comp.train(titles[:100])
    partial = new_comp.compress(titles[:80])      # 20 ids still "in flight"
    with store._lock:
        store._swap_state_locked(new_comp, partial)
        assert store.n_strings == 100             # acknowledged ids stay


def test_extend_reparses_when_compact_swaps_mid_encode(artifact, titles):
    # simulate a compact() landing between extend()'s encode and ingest by
    # bumping version_id after the first encode call
    store = _mutable(artifact, titles[:100])
    real_encode = store._encoder.encode
    tripped = {}

    class Tripwire:
        def encode(self, strings):
            if not tripped:
                tripped["hit"] = True
                corpus = real_encode(strings)
                store.compact()          # swaps dictionary + version_id
                return corpus            # now-stale payloads
            return store._encoder.encode(strings)  # post-swap encoder

    store._encoder = Tripwire()
    ids = store.extend([b"raced string", titles[5]])
    assert tripped and store.multiget(ids) == [b"raced string", titles[5]]


# ------------------------------------------------- acceptance criterion
def test_acceptance_full_lifecycle(titles, tmp_path):
    """N build + M frozen-dict appends + injected drift + compact: every
    read path returns byte-identical strings, before and after save→open."""
    N, M = 500, 300
    base = titles[:N]
    appended = titles[N : N + M - 150] + _junk(150, length=160, seed=7)
    art = registry.train("onpair16", base, sample_bytes=SAMPLE)
    store = MutableStringStore(art, Encoder(art).encode(base),
                               strings_per_segment=SPS)
    store.extend(appended)
    expect = base + appended
    assert store.drift.should_compact()           # injected drift visible
    store.compact()

    def check(s):
        n = s.n_strings
        assert n == N + M
        assert s.scan(0, n) == expect
        rng = np.random.default_rng(2)
        ids = rng.integers(0, n, 400).tolist()
        assert s.multiget(ids) == [expect[i] for i in ids]
        for i in (0, N - 1, N, n - 1):
            assert s.get(i) == expect[i]

    check(store)
    d = str(tmp_path / "acceptance")
    store.save(d)
    check(MutableStringStore.open(d))


def test_persisted_pallas_encoder_raises_without_kernels(artifact, titles,
                                                         tmp_path,
                                                         monkeypatch):
    """A saved encode_backend='pallas' that this host cannot honour raises
    on open instead of quietly parsing on numpy."""
    store = _mutable(artifact, titles[:300])
    store.save(str(tmp_path))
    meta_path = os.path.join(
        CompressedStringStore._resolve_current(str(tmp_path)), "store.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["encode_backend"] = "pallas"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    monkeypatch.setenv("REPRO_NO_JAX", "1")
    with pytest.raises(ValueError, match="encode_backend='pallas'"):
        MutableStringStore.open(str(tmp_path))
    reopened = MutableStringStore.open(str(tmp_path), encode_backend="numpy")
    assert reopened.multiget([0, 299]) == [titles[0], titles[299]]
