"""A store multiget decodes all its misses in one device call.

``OnPairDevice.multiget_decode`` cuts the misses into the buckets' padded
batches, the same batches the store cut before. It launches every batch
before it reads any back, and the bytes come back as uint8. Checked on the
CPU (Pallas interpreted, and the jitted reference), for OnPair16 and for
chained (unbounded OnPair) dictionaries:

- the strings, against the host decoder, in input order;
- the store's counters, against decoding the same misses one chunk a call;
- the overlap counter;
- a wrapper on ``multiget_decode`` like the one that counts a benchmark's
  tokens.
"""

import numpy as np
import pytest

from repro.data.synth import load_dataset
from repro.kernels import ops
from repro.store import CompressedStringStore

#: rows of every device batch: small, so that one bucket takes several
BATCH = 8
#: the store counters a decode adds to
COUNTERS = ("batches", "padded_rows", "decoded_strings", "real_tokens",
            "dict_rows", "h2d_bytes", "d2h_bytes", "device_strings")
#: strings of the corpus made empty
EMPTY = (4, 11, 40)


@pytest.fixture(scope="module")
def urls():
    strings = load_dataset("urls", 1 << 17, seed=6)
    for i in EMPTY:
        strings[i] = b""
    return strings


@pytest.fixture(scope="module", params=["onpair16", "onpair"])
def store(request, urls):
    codec = None if request.param == "onpair16" else "onpair"
    store = CompressedStringStore.build(urls, codec=codec,
                                        sample_bytes=1 << 17, cache_bytes=0,
                                        batch_size=BATCH)
    if store.backend != "jax":
        pytest.skip("jax backend unavailable")
    assert store._device.chained == (codec == "onpair")
    return store


def _tokens(store, ids) -> list[np.ndarray]:
    return [np.asarray(store.segments.string_tokens(i), dtype=np.int32)
            for i in ids]


def _rows(store, ids) -> np.ndarray:
    lists = _tokens(store, ids)
    return store.dictionary.stream_rows(
        np.concatenate(lists), np.cumsum([0] + [t.size for t in lists]))


def _ids(store, n: int = 60) -> list[int]:
    """Random ids with the empty strings, the string of most rows and
    duplicates among them: their misses fill at least two buckets, and one
    bucket with more than one batch."""
    rng = np.random.default_rng(9)
    most = int(np.argmax(store._string_rows(store.corpus)))
    ids = rng.integers(0, store.n_strings, n).tolist() + list(EMPTY) + [most]
    ids += ids[:5]
    buckets = np.searchsorted(store.bucket_caps,
                              _rows(store, list(dict.fromkeys(ids))))
    sizes = np.bincount(buckets)
    assert np.count_nonzero(sizes) >= 2 and sizes.max() > BATCH
    return ids


def _counters(store) -> dict:
    snap = store.stats_snapshot()
    return {k: snap[k] for k in COUNTERS} | {
        "shapes": dict(store.stats.batches_by_shape)}


def _delta(after: dict, before: dict) -> dict:
    shapes = {s: n - before["shapes"].get(s, 0)
              for s, n in after["shapes"].items()}
    return {k: after[k] - before[k] for k in COUNTERS} | {
        "shapes": {s: n for s, n in shapes.items() if n}}


def _chunk_at_a_time(store, misses: list[int]) -> tuple[list[bytes], dict]:
    """The misses decoded one device call a chunk, as the store once called
    the device: its buckets, chunks of ``batch_size``, and the counters
    those calls add up to."""
    dev, lists, rows = store._device, _tokens(store, misses), \
        _rows(store, misses)
    buckets = np.searchsorted(store.bucket_caps, rows, side="left")
    out = [None] * len(misses)
    got = dict.fromkeys(COUNTERS, 0) | {"shapes": {}}
    for b in np.unique(buckets):
        cap = int(store.bucket_caps[b])
        members = np.flatnonzero(buckets == b)
        for c0 in range(0, members.size, BATCH):
            chunk = members[c0 : c0 + BATCH]
            h2d, d2h = dev.h2d_bytes, dev.d2h_bytes
            decoded = dev.multiget_decode([lists[k] for k in chunk],
                                          pad_tokens=cap, pad_batch=BATCH,
                                          use_pallas=store.use_pallas)
            for k, v in zip(chunk, decoded):
                out[k] = v
            shape = (BATCH, cap)
            got["shapes"][shape] = got["shapes"].get(shape, 0) + 1
            got["batches"] += 1
            got["padded_rows"] += BATCH
            got["decoded_strings"] += chunk.size
            got["device_strings"] += chunk.size
            got["real_tokens"] += sum(lists[k].size for k in chunk)
            got["dict_rows"] += int(rows[chunk].sum())
            got["h2d_bytes"] += dev.h2d_bytes - h2d
            got["d2h_bytes"] += dev.d2h_bytes - d2h
    return out, got


@pytest.mark.parametrize("use_pallas", [True, False])
def test_multiget_equals_host_decoder_in_input_order(store, urls,
                                                      use_pallas,
                                                      monkeypatch):
    monkeypatch.setattr(store, "use_pallas", use_pallas)
    ids = _ids(store)
    expect = [store.dictionary.decode_tokens(t) for t in _tokens(store, ids)]
    assert expect == [urls[i] for i in ids]
    before = _counters(store)
    assert store.multiget(ids) == expect
    assert _delta(_counters(store), before)["batches"] > 2


@pytest.mark.parametrize("use_pallas", [True, False])
def test_counters_equal_a_decode_one_chunk_a_call(store, use_pallas,
                                                  monkeypatch):
    monkeypatch.setattr(store, "use_pallas", use_pallas)
    ids = _ids(store)
    misses = list(dict.fromkeys(ids))
    before = _counters(store)
    got = store.multiget(ids)
    one_call = _delta(_counters(store), before)
    strings, chunked = _chunk_at_a_time(store, misses)
    assert got == [strings[misses.index(i)] for i in ids]
    assert one_call == chunked
    # bytes come back, not int32 words: 16 a row of each batch's [B, 16 T]
    # output, a quarter of the words, and no lengths beside them
    assert one_call["d2h_bytes"] == sum(
        n * b * 16 * t for (b, t), n in one_call["shapes"].items())
    # tokens and their counts go in as int32, as before
    assert one_call["h2d_bytes"] == sum(
        n * 4 * b * (t + 1) for (b, t), n in one_call["shapes"].items())


@pytest.mark.parametrize("n_ids", [1, 60])
def test_overlap_counter_counts_every_batch_after_the_first(store, n_ids):
    ids = _ids(store)[:n_ids]
    before, over = _counters(store), ops._OVERLAPPED.value
    store.multiget(ids)
    batches = _delta(_counters(store), before)["batches"]
    assert batches == 1 if n_ids == 1 else batches > 2
    assert ops._OVERLAPPED.value - over == batches - 1


def test_a_wrapper_on_multiget_decode_sees_every_decoded_token(store,
                                                               monkeypatch):
    """One call a store multiget carries every miss's tokens, as a list of
    token arrays, and returns a list of bytes."""
    # a chained store compiles its buckets, by empty calls, before its first
    # decode: do that first, as a served store has
    store.warm_decode()
    dev = store._device
    inner, seen = dev.multiget_decode, []

    def wrapped(token_lists, *args, **kw):
        seen.append(sum(len(t) for t in token_lists))
        out = inner(token_lists, *args, **kw)
        assert isinstance(out, list) and all(type(v) is bytes for v in out)
        return out

    monkeypatch.setattr(dev, "multiget_decode", wrapped)
    before = _counters(store)
    store.multiget(_ids(store))
    assert len(seen) == 1
    assert seen[0] == _delta(_counters(store), before)["real_tokens"] > 0


def test_multiget_decode_reports_each_batch_once(store):
    """Given the buckets' widths, the codec cuts the streams into the
    buckets' batches itself, and hears of each one with its members."""
    dev = store._device
    misses = list(dict.fromkeys(_ids(store)))
    lists, rows = _tokens(store, misses), _rows(store, misses)
    heard = []
    out = dev.multiget_decode(
        lists, pad_tokens=store.bucket_caps, pad_batch=BATCH, rows=rows,
        on_batch=lambda *a: heard.append(a))
    assert out == [store.dictionary.decode_tokens(t) for t in lists]
    members = np.concatenate([m for _, m, _, _, _, _ in heard])
    assert sorted(members.tolist()) == list(range(len(misses)))
    for shape, m, decoded, seconds, h2d, d2h in heard:
        cap = shape[1]
        assert shape[0] == BATCH and cap in store.bucket_caps
        assert (rows[m] <= cap).all() and m.size <= BATCH
        assert decoded == [out[k] for k in m]
        assert seconds > 0 and h2d == 4 * BATCH * (cap + 1)
        assert d2h == BATCH * 16 * cap
    # no widths: one batch shaped by the lists, as before
    heard.clear()
    assert dev.multiget_decode(lists, on_batch=lambda *a: heard.append(a)) \
        == out
    assert [(h[0], h[1].size) for h in heard] == [
        ((len(lists), int(rows.max())), len(lists))]
    # rows counted for more streams than are passed (a wrapper that passes
    # on a prefix of the lists): the first streams' rows count
    half = len(lists) // 2
    assert dev.multiget_decode(lists[:half], pad_tokens=store.bucket_caps,
                               pad_batch=BATCH, rows=rows) == out[:half]
    # a stream wider than the widest bucket is refused before any launch
    with pytest.raises(ValueError, match="rows"):
        dev.multiget_decode(lists, pad_tokens=store.bucket_caps[:1] - 1)
