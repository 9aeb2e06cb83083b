"""API v2 tests: serializable dictionary artifacts, the codec registry and
its capability flags, Encoder/Decoder backends, and store/sharded-store
persistence. Everything here must run on a numpy-only host (no jax, no
hypothesis, no zstandard) — jax-dependent paths are skip-gated."""

import json
import os

import numpy as np
import pytest

from repro.core import (CompressedCorpus, DictArtifact, Decoder, Encoder,
                        registry)
from repro.data.synth import load_dataset
from repro.distributed import ShardedStringStore, plan_shards, save_sharded
from repro.store import CompressedStringStore

SAMPLE = 1 << 18


def _available_codecs():
    return registry.names()  # zstd-block drops out when zstandard is missing


@pytest.fixture(scope="module")
def titles():
    strings = load_dataset("book_titles", SAMPLE)
    strings[3] = b""                      # empties survive round-trips
    strings[7] = b"\x00\xff" * 9          # binary-safe
    return strings


@pytest.fixture(scope="module")
def artifacts(titles):
    """codec name -> (artifact, corpus) trained once per module."""
    out = {}
    for name in _available_codecs():
        art = registry.train(name, titles, sample_bytes=SAMPLE) \
            if registry.capabilities(name).trainable \
            else registry.create(name).to_artifact()
        corpus = Encoder(art).encode(titles)
        out[name] = (art, corpus)
    return out


# ----------------------------------------------------------------- registry
def test_all_codecs_constructible_by_name():
    # acceptance criterion: the paper's six rows all come from the registry
    for name in ("onpair", "onpair16", "bpe", "fsst", "lz-block", "raw"):
        codec = registry.create(name)
        assert hasattr(codec, "train") and hasattr(codec, "compress")


def test_registry_aliases_and_unknown():
    assert registry.resolve("zlib-block") == "lz-block"
    with pytest.raises(KeyError):
        registry.resolve("nope-codec")


def test_capability_flags_match_behavior(titles, artifacts):
    for name, (art, corpus) in artifacts.items():
        caps = registry.capabilities(name)
        dec = Decoder(art)

        # trainable <=> the artifact carries a real trained table
        assert caps.trainable == (art.num_entries > 0), name

        # token_stream <=> per-string payload slices are u16 token streams
        # decodable against the frozen dictionary
        if caps.token_stream:
            lens = np.diff(corpus.offsets)
            assert (lens % 2 == 0).all(), name
            d = dec.dictionary
            assert d is not None, name
            for i in (0, 3, 7, len(titles) - 1):
                toks = np.asarray(corpus.string_tokens(i), dtype=np.int64)
                assert d.decode_tokens(toks) == titles[i], name
        else:
            assert dec.dictionary is None or name == "fsst", name

        # bounded_entries <=> every table entry fits the 16-byte decode row
        if art.entries:
            assert caps.bounded_entries == all(
                len(e) <= 16 for e in art.entries), name

        # device-decodable implies a token stream; device-encodable implies
        # bounded entries. When jax is importable the device codec must
        # actually construct, and the device encoder exactly where both hold
        if caps.device_decodable:
            assert caps.token_stream, name
            jax = pytest.importorskip("jax")  # noqa: F841
            from repro.kernels.ops import OnPairDevice
            OnPairDevice.from_artifact(art)
            if caps.bounded_entries:
                Encoder(art, backend="pallas")
            else:
                with pytest.raises(ValueError, match="16"):
                    Encoder(art, backend="pallas")


# ----------------------------------------------------- artifact persistence
def test_artifact_save_load_decode_identical(titles, artifacts, tmp_path):
    # acceptance criterion: train -> save -> load -> decode, byte-identical,
    # for every registered codec
    expect = b"".join(titles)
    for name, (art, corpus) in artifacts.items():
        path = str(tmp_path / f"{name}.rpa")
        art.save(path)
        loaded = DictArtifact.load(path)
        assert registry.resolve(loaded.codec) == name
        assert loaded.entries == art.entries
        assert loaded.config == art.config
        dec = Decoder(loaded)
        assert dec.decode_all(corpus) == expect, name
        for i in (0, 3, 7, 42, len(titles) - 1):
            assert dec.access(corpus, i) == titles[i], name
        # and an encoder from the loaded artifact reproduces the corpus
        corpus2 = Encoder(loaded).encode(titles)
        assert corpus2.payload.tobytes() == corpus.payload.tobytes(), name
        np.testing.assert_array_equal(corpus2.offsets, corpus.offsets)


def test_artifact_bytes_roundtrip_and_bad_magic(artifacts):
    art, _ = artifacts["onpair16"]
    blob = art.to_bytes()
    again = DictArtifact.from_bytes(blob)
    assert again.entries == art.entries
    with pytest.raises(ValueError):
        DictArtifact.from_bytes(b"not an artifact container at all")


def test_artifact_mmap_load_is_lazy(artifacts, tmp_path):
    art, _ = artifacts["onpair16"]
    path = str(tmp_path / "d.rpa")
    art.save(path)
    loaded = DictArtifact.load(path, mmap=True)
    assert isinstance(loaded.arrays["blob"], np.memmap)
    assert loaded.entries == art.entries


def test_corpus_save_load(titles, artifacts, tmp_path):
    for name in ("onpair16", "lz-block", "raw"):
        art, corpus = artifacts[name]
        path = str(tmp_path / f"{name}.rpc")
        corpus.save(path)
        loaded = CompressedCorpus.load(path)
        assert loaded.raw_bytes == corpus.raw_bytes
        assert loaded.payload.tobytes() == corpus.payload.tobytes()
        np.testing.assert_array_equal(loaded.offsets, corpus.offsets)
        assert Decoder(art).decode_all(loaded) == b"".join(titles), name


def test_block_corpus_meta_arrays_survive(titles, artifacts, tmp_path):
    art, corpus = artifacts["lz-block"]
    codec = registry.codec_from_artifact(art)
    codec.access(corpus, 5)                      # populates "_cache" meta
    path = str(tmp_path / "b.rpc")
    corpus.save(path)
    loaded = CompressedCorpus.load(path)
    assert "_cache" not in loaded.meta           # transient state dropped
    for k in ("str_block", "str_off", "str_len"):
        np.testing.assert_array_equal(np.asarray(loaded.meta[k]),
                                      np.asarray(corpus.meta[k]))
    assert registry.codec_from_artifact(art).access(loaded, 5) == titles[5]


# ------------------------------------------------------- encoder / decoder
def test_backend_validation(artifacts):
    art16, _ = artifacts["onpair16"]
    art_raw, _ = artifacts["raw"]
    with pytest.raises(ValueError):
        Decoder(art16, backend="cuda")
    with pytest.raises(ValueError):
        Decoder(art_raw, backend="pallas")   # not device-decodable


def test_pallas_backend_matches_numpy(titles, artifacts):
    pytest.importorskip("jax")
    art, corpus = artifacts["onpair16"]
    ids = list(range(0, 200, 7))
    host = Decoder(art, backend="numpy")
    dev = Decoder(art, backend="pallas")
    assert dev.multiget(corpus, ids) == host.multiget(corpus, ids)
    assert dev.access(corpus, 3) == titles[3]


# ------------------------------------------------------- store persistence
def test_store_save_open_multiget_identical(titles, tmp_path):
    # acceptance criterion: a saved store reopened from disk serves identical
    # get/multiget/scan without retraining
    store = CompressedStringStore.build(titles, sample_bytes=SAMPLE,
                                        strings_per_segment=512)
    d = str(tmp_path / "store")
    store.save(d)
    reopened = CompressedStringStore.open(d)
    rng = np.random.default_rng(11)
    ids = rng.integers(0, len(titles), 800).tolist()
    assert reopened.multiget(ids) == store.multiget(ids)
    assert reopened.get(7) == store.get(7)
    assert reopened.scan(400, 700) == store.scan(400, 700)
    # saved construction params come back
    assert reopened.segments.strings_per_segment == 512
    with open(os.path.join(d, "store.json")) as f:
        meta = json.load(f)
    assert meta["codec"] == "onpair16" and meta["n_strings"] == len(titles)


def test_store_accepts_artifact_directly(titles, artifacts):
    art, corpus = artifacts["onpair16"]
    store = CompressedStringStore(art, corpus, cache_bytes=0)
    assert store.get(12) == titles[12]
    assert store.artifact is art


def test_store_rejects_non_token_codec(titles, artifacts):
    art, corpus = artifacts["lz-block"]
    with pytest.raises(ValueError):
        CompressedStringStore(art, corpus)


def test_store_build_by_codec_name(titles):
    store = CompressedStringStore.build(titles, codec="bpe",
                                        sample_bytes=1 << 16)
    assert store.compressor.name == "bpe"
    assert store.get(3) == titles[3]


# --------------------------------------------------------- sharded persistence
def test_plan_shards_covers_everything():
    assert plan_shards(10, 4, 3) == [(0, 4), (4, 8), (8, 10)]
    assert plan_shards(3, 10, 5) == [(0, 3)]       # never more shards than segs
    assert plan_shards(0, 4, 2) == [(0, 0)]
    with pytest.raises(ValueError):
        plan_shards(10, 4, 0)


def test_sharded_store_roundtrip(titles, tmp_path):
    store = CompressedStringStore.build(titles, sample_bytes=SAMPLE,
                                        strings_per_segment=256)
    d = str(tmp_path / "shards")
    bounds = save_sharded(store, d, 4)
    assert bounds[0][0] == 0 and bounds[-1][1] == len(titles)
    sharded = ShardedStringStore.open(d)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, len(titles), 600).tolist()
    assert sharded.multiget(ids) == store.multiget(ids)
    assert sharded.get(0) == titles[0]
    with pytest.raises(IndexError):
        sharded.get(len(titles))


# -------------------------------------------------------------- pack_corpus
def test_pack_corpus_single_allocation_matches_join():
    from repro.core.api import pack_corpus
    parts = [b"", b"abc", b"\x00" * 40, b"z"]
    corpus = pack_corpus(parts, raw_bytes=44)
    assert corpus.payload.tobytes() == b"".join(parts)
    np.testing.assert_array_equal(corpus.offsets, [0, 0, 3, 43, 44])
    assert pack_corpus([], 0).payload.size == 0
