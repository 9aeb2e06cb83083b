"""Unbounded OnPair on the device: dictionaries with entries over 16 bytes
decode as chains of 16-byte rows.

Every device decode entry point (``multiget_decode``, ``decode_batch``,
``decode_stream``; interpreted on the CPU) is checked byte for byte against
the plain reference, the host decoder ``PackedDictionary.decode_tokens``
over the blob and offsets, on seeded random dictionaries whose entries run
from 1 to 200 bytes. An OnPair16 dictionary must come out exactly as
before: the same device tables, bucket caps and decode shapes, and no
expansion. Device encode of a chained dictionary raises.
"""

import numpy as np
import pytest

from repro.client import connect
from repro.core import Encoder, make_onpair16, registry
from repro.core.packed import PackedDictionary
from repro.data.synth import load_dataset
from repro.kernels import onpair_decode, ops
from repro.kernels.ops import OnPairDevice, expand_rows, row_counts
from repro.kernels.ref import DeviceDict
from repro.net.shard_server import ShardServer
from repro.store import CompressedStringStore, MutableStringStore

#: entry lengths of the random dictionaries: every length up to one row,
#: then lengths on, just past and well past the 16-byte row boundary
LENGTHS = list(range(1, 17)) + [17, 32, 33, 71, 128, 200]


def _random_dictionary(seed: int, per_length: int = 6) -> PackedDictionary:
    rng = np.random.default_rng(seed)
    entries = [bytes([b]) for b in range(256)]
    for length in LENGTHS:
        for _ in range(per_length):
            entries.append(rng.integers(0, 256, length,
                                        dtype=np.uint8).tobytes())
    order = rng.permutation(len(entries))
    return PackedDictionary.build([entries[i] for i in order])


def _random_streams(d: PackedDictionary, seed: int, n: int = 20
                    ) -> list[np.ndarray]:
    """Token streams of 0-9 tokens, one empty and one long string among
    them, each long token's rows crossing a 128-lane window."""
    rng = np.random.default_rng(seed)
    streams = [rng.integers(0, d.num_entries, rng.integers(0, 10),
                            dtype=np.int32) for _ in range(n)]
    streams[1] = np.zeros(0, dtype=np.int32)
    longest = np.argsort(d.lens)[-4:].astype(np.int32)
    streams[2] = np.concatenate([longest, longest[::-1]])
    return streams


@pytest.fixture(scope="module", params=[0, 1])
def chained(request):
    d = _random_dictionary(request.param)
    assert d.row_count is not None and int(d.lens.max()) == 200
    return d, OnPairDevice(d), _random_streams(d, request.param + 10)


def _expect(d: PackedDictionary, streams) -> list[bytes]:
    return [d.decode_tokens(s) for s in streams]


def _rows(d: PackedDictionary, streams) -> np.ndarray:
    return d.stream_rows(np.concatenate(streams),
                         np.cumsum([0] + [len(s) for s in streams]))


# ------------------------------------------------------------------ layout
def test_chained_rows_hold_every_entry(chained):
    d, dev, _ = chained
    rows, row_lens = d.chained_rows()
    n = d.num_entries
    assert rows.shape == (d.num_rows, 16) and d.num_rows > n
    assert np.array_equal(rows[:n], d.mat16)
    for t, e in enumerate(d.entries):
        chain = [t] + list(range(d.row_next[t],
                                 d.row_next[t] + d.row_count[t] - 1))
        assert d.row_count[t] == max(1, -(-len(e) // 16))
        assert b"".join(rows[r, :row_lens[r]].tobytes() for r in chain) == e
    # device tables: the rows and each row's byte count
    assert np.array_equal(np.asarray(dev.dd.mat16), rows.astype(np.int32))
    assert np.array_equal(np.asarray(dev.dd.lens), row_lens)
    assert dev.chained


def test_expand_rows_follows_each_chain(chained):
    d, dev, streams = chained
    tokens, n_tokens = ops.pack_token_matrix(streams)
    counts = row_counts(tokens, n_tokens, d.row_count)
    width = int(counts.sum(axis=1).max()) + 3
    rows, n_rows = expand_rows(tokens, counts, d.row_next, width)
    for i, s in enumerate(streams):
        chain = [r for t in s.tolist() for r in
                 [t] + list(range(d.row_next[t],
                                  d.row_next[t] + d.row_count[t] - 1))]
        assert rows[i].tolist() == chain + [0] * (width - len(chain))
        assert n_rows[i] == len(chain)
    assert np.array_equal(n_rows, _rows(d, streams))
    # with no width asked for, the most rows of a stream
    tight, _ = expand_rows(tokens, counts, d.row_next)
    assert tight.shape[1] == int(n_rows.max())


# ------------------------------------------------------------------ decode
@pytest.mark.parametrize("use_pallas", [True, False])
def test_multiget_decode_equals_host_decoder(chained, use_pallas):
    d, dev, streams = chained
    assert dev.multiget_decode(streams, use_pallas=use_pallas) == \
        _expect(d, streams)
    # at a store bucket's static shape: rows bound T, padding rows empty
    cap = int(_rows(d, streams).max()) + 5
    assert dev.multiget_decode(streams, pad_tokens=cap, pad_batch=24,
                               use_pallas=use_pallas) == _expect(d, streams)
    with pytest.raises(ValueError, match="rows"):
        dev.multiget_decode(streams, pad_tokens=cap - 6)
    # rows a caller has counted are taken as given, not counted again
    with pytest.raises(ValueError, match="rows"):
        dev.multiget_decode(streams, pad_tokens=cap,
                            rows=_rows(d, streams) + 6)



def test_decode_batch_takes_token_streams(chained):
    d, dev, streams = chained
    tokens, n_tokens = ops.pack_token_matrix(streams, pad_batch=24)
    width = int(_rows(d, streams).max())
    got = dev.decode_batch(tokens, n_tokens, 16 * width)
    assert got[:len(streams)] == _expect(d, streams)
    assert got[len(streams):] == [b""] * (24 - len(streams))
    # the output bound is in bytes, as for OnPair16: a string past it is cut
    short = dev.decode_batch(tokens, n_tokens, 40)
    assert short[2] == _expect(d, streams)[2][:40]


def test_long_strings_cross_lane_windows(chained):
    d, dev, streams = chained
    long = _expect(d, [streams[2]])[0]
    assert len(long) > 3 * onpair_decode.LANES
    assert dev.multiget_decode([streams[2], streams[1]]) == [long, b""]


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("tile", [64, 256])
def test_decode_stream_equals_host_decoder(chained, use_pallas, tile):
    d, dev, streams = chained
    flat = np.concatenate(streams)
    assert dev.decode_stream(flat, use_pallas=use_pallas, tile=tile) == \
        d.decode_tokens(flat)
    assert dev.decode_stream(np.zeros(0, np.int32)) == b""


def test_counters_count_rows_and_long_tokens(chained):
    d, dev, streams = chained
    rows0, long0 = ops._DICT_ROWS.value, ops._LONG_TOKENS.value
    dev.multiget_decode(streams)
    flat = np.concatenate(streams)
    assert ops._DICT_ROWS.value - rows0 == int(d.row_count[flat].sum())
    assert ops._LONG_TOKENS.value - long0 == int(np.count_nonzero(
        d.lens[flat] > 16))


def test_dictionary_over_the_vmem_budget_is_refused(chained, monkeypatch):
    d, _, _ = chained
    need = onpair_decode.packed_bytes(d.num_rows)
    monkeypatch.setattr(onpair_decode, "DICT_VMEM_BYTES", need - 1)
    with pytest.raises(ValueError, match="VMEM"):
        OnPairDevice(d)
    monkeypatch.setattr(onpair_decode, "DICT_VMEM_BYTES", need)
    OnPairDevice(d)


# ------------------------------------------------------------------ encode
def test_device_encode_of_chained_dictionary_raises(chained):
    d, dev, _ = chained
    with pytest.raises(ValueError, match="16 B"):
        dev.encode_batch([b"abc"])
    with pytest.raises(ValueError, match="16 B"):
        dev.encode_bucketed([b"abc"])
    with pytest.raises(ValueError, match="16 B"):
        dev.warm_encode()
    art = d.to_artifact()
    assert art.codec == "onpair"
    with pytest.raises(ValueError, match="bounded_entries"):
        Encoder(art, backend="pallas")
    with pytest.raises(ValueError, match="bounded_entries"):
        Encoder(art, backend="pallas", device=dev)
    with pytest.raises(ValueError, match="bounded_entries"):
        MutableStringStore(art, encode_backend="pallas")
    # the host encoder and the default mutable store are unaffected
    store = MutableStringStore(art, cache_bytes=0)
    ids = store.extend([d.entries[5] * 3, b""])
    assert store.multiget(ids) == [d.entries[5] * 3, b""]


# ------------------------------------------------------------------- store
@pytest.fixture(scope="module")
def urls():
    return load_dataset("urls", 1 << 18, seed=3)


def test_onpair_store_resolves_to_jax_and_decodes(urls, tmp_path_factory,
                                                 monkeypatch):
    store = CompressedStringStore.build(urls, codec="onpair",
                                        sample_bytes=1 << 18, cache_bytes=0,
                                        batch_size=64)
    assert store.backend == "jax" and store._device.chained
    assert registry.capabilities("onpair").device_decodable
    # buckets count rows: the largest covers the string of most rows
    rows = store._string_rows(store.corpus)
    assert int(rows.max()) <= int(store.bucket_caps[-1])
    assert (rows >= store.corpus.token_counts()).all()
    assert (rows > store.corpus.token_counts()).any()
    rng = np.random.default_rng(5)
    ids = rng.integers(0, len(urls), 400).tolist()
    assert store.multiget(ids) == [urls[i] for i in ids]
    snap = store.stats_snapshot()
    # every bucket shape compiled before the first decode, none after
    assert set(snap["first_batch_s"]) == {f"64x{c}" for c in
                                          snap["bucket_caps"]}
    assert {t for _, t in store.stats.jit_shapes} <= set(store.bucket_caps)
    assert snap["dict_rows"] > snap["real_tokens"] > 0
    assert snap["device_strings"] == snap["decoded_strings"]
    # saved, then opened for serving, it answers over tcp:// from the
    # device kernel
    d = str(tmp_path_factory.mktemp("onpair_store"))
    store.save(d)
    calls = []
    inner = onpair_decode.decode_compact

    def spy(*args, **kw):
        calls.append(args[0].shape)
        return inner(*args, **kw)

    monkeypatch.setattr(onpair_decode, "decode_compact", spy)
    with ShardServer.from_dir(d, read_only=True).start() as server:
        assert server.store.backend == "jax"
        with connect(f"tcp://127.0.0.1:{server.port}") as client:
            assert client.multiget(ids[:50]) == [urls[i] for i in ids[:50]]
        assert server.store.stats.device_strings == 50
    assert calls


# ------------------------------------------------------- OnPair16 unchanged
@pytest.fixture(scope="module")
def store16():
    titles = load_dataset("book_titles", 1 << 18, seed=4)
    comp = make_onpair16(sample_bytes=1 << 18, seed=1)
    comp.train(titles)
    return titles, CompressedStringStore(comp, comp.compress(titles),
                                         cache_bytes=0, batch_size=32)


def test_stream_rows_are_token_counts_for_onpair16(store16):
    _, store = store16
    streams = [np.arange(k, dtype=np.int32) for k in (0, 3, 7)]
    assert _rows(store.dictionary, streams).tolist() == [0, 3, 7]


def test_onpair16_tables_caps_and_shapes_unchanged(store16, monkeypatch):
    titles, store = store16
    d = store.dictionary
    # the device tables as they were built before chained rows: the
    # (N, 16) matrix and the entry lengths, no chain tables
    dd = DeviceDict.build(d)
    assert np.asarray(dd.mat16).dtype == np.int32
    assert np.array_equal(np.asarray(dd.mat16), d.mat16.astype(np.int32))
    assert np.array_equal(np.asarray(dd.lens), d.lens.astype(np.int32))
    assert d.row_count is None and d.num_rows == d.num_entries
    assert not store._device.chained
    # bucket caps: token-count quantiles, as before
    counts = store.corpus.token_counts()
    assert np.array_equal(store._string_rows(store.corpus), counts)
    caps = sorted({max(8, (int(np.quantile(counts, q)) + 7) // 8 * 8)
                   for q in (0.5, 0.9, 0.99, 1.0)})
    if caps[-1] < counts.max():
        caps.append(max(8, (int(counts.max()) + 7) // 8 * 8))
    assert store.bucket_caps.tolist() == caps[-4:]

    # decode shapes and copies: (batch, token cap) int32 matrices straight
    # to the kernel, never expanded
    def no_expansion(*args, **kw):
        raise AssertionError("an OnPair16 stream was expanded")

    monkeypatch.setattr(ops, "expand_rows", no_expansion)
    seen = []
    inner = onpair_decode.decode_compact

    def spy(tokens, n_tokens, mat16, lens, max_out):
        seen.append((tokens.shape, mat16.shape, max_out))
        return inner(tokens, n_tokens, mat16, lens, max_out)

    monkeypatch.setattr(onpair_decode, "decode_compact", spy)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, len(titles), 200).tolist()
    h2d = store._device.h2d_bytes
    assert store.multiget(ids) == [titles[i] for i in ids]
    assert store.stats.first_batch_s.keys() == store.stats.jit_shapes
    assert {shape for shape, _, _ in seen} == store.stats.jit_shapes
    assert all(m == (d.num_entries, 16) and out == 16 * shape[1]
               for shape, m, out in seen)
    assert store._device.h2d_bytes - h2d == sum(
        4 * b * (t + 1) for (b, t), _, _ in seen)
    assert store.stats.dict_rows == store.stats.real_tokens
