"""Write-path encode tests.

Covers the batched frozen-dictionary parser (vectorised table walk vs the
per-string DynamicLPM oracle), pallas-vs-numpy byte identity through the
full mutable lifecycle (extend -> seal -> save -> open -> multiget), the
bounded compact-race retry, the non-token-stream refusal, client-side
group-commit, and the jit-retrace bound on the device encode path.

Importable without jax: device-path tests skip when the kernels are
unavailable (REPRO_NO_JAX or no jax install), everything else runs on numpy
alone.
"""

import os

import numpy as np
import pytest

from repro.client import connect, wrap
from repro.client.session import _ExtendBatcher
from repro.core import registry
from repro.core.api import RawCompressor
from repro.core.codec import Encoder
from repro.core.lpm import parse_batch
from repro.data.synth import load_dataset
from repro.net import ShardServer
from repro.store.mutable import MutableStringStore
from repro.store.store import device_codec

SAMPLE = 1 << 18

#: the shapes the paper's bound makes interesting: empty, single byte,
#: exactly one max-length entry, longer than any entry, every byte value
EDGE = [b"", b"a", b"x" * 16, b"y" * 40, bytes(range(256))]

OnPairDevice = device_codec()
needs_jax = pytest.mark.skipif(OnPairDevice is None,
                               reason="jax unavailable (or REPRO_NO_JAX)")


@pytest.fixture(scope="module")
def titles():
    return load_dataset("book_titles", SAMPLE)


@pytest.fixture(scope="module")
def artifact(titles):
    return registry.train("onpair16", titles, sample_bytes=SAMPLE, seed=3)


# --------------------------------------------------- vectorised batch parse
@pytest.mark.parametrize("codec", ["onpair16", "onpair"])
def test_parse_batch_matches_per_string_lpm(titles, codec):
    """The shared table walk is byte-identical to the greedy per-string
    parse — same tokens, same tie-breaks — for bounded AND unbounded
    dictionaries, on real data plus the edge shapes."""
    comp = registry.create(codec, sample_bytes=SAMPLE // 2)
    comp.train(titles)
    batch = titles[:512] + EDGE
    ref = [np.asarray(comp._parser().parse(s), dtype="<u2") for s in batch]
    payload, counts = parse_batch(comp.dictionary, batch)
    off = np.concatenate(([0], np.cumsum(counts)))
    for i in range(len(batch)):
        assert np.array_equal(payload[off[i]:off[i + 1]], ref[i]), \
            f"{codec}: mismatch at string {i}: {batch[i][:40]!r}"


def test_encoder_batch_equals_encode_one(artifact, titles):
    enc = Encoder(artifact)
    batch = titles[:64] + EDGE
    corpus = enc.encode(batch)
    assert corpus.n_strings == len(batch)
    for i, s in enumerate(batch):
        assert corpus.string_payload(i) == enc.encode_one(s)


# ----------------------------------------------------- constructor refusals
def test_mutable_refuses_non_token_stream():
    raw = RawCompressor()
    raw.train([b"abc"])
    with pytest.raises(ValueError, match="token-stream"):
        MutableStringStore(raw)


def test_mutable_refuses_unknown_encode_backend(artifact):
    with pytest.raises(ValueError, match="encode_backend"):
        MutableStringStore(artifact, encode_backend="cuda")


# ------------------------------------------------------ bounded retry loop
def test_extend_retry_is_bounded(artifact, titles):
    """A compact() landing between parse and ingest forces a re-parse; when
    every optimistic attempt loses, the final attempt encodes under the
    store lock — extend() terminates instead of livelocking."""
    store = MutableStringStore(artifact)
    real = store._encoder
    calls = {"n": 0}

    class Flapping:
        def encode(self, strings):
            calls["n"] += 1
            store.version_id += 1  # a compact swaps the generation mid-parse
            return real.encode(strings)

    store._encoder = Flapping()
    batch = titles[:8]
    ids = store.extend(batch)
    assert ids == list(range(8))
    assert calls["n"] == store._MAX_ENCODE_RETRIES + 1
    store._encoder = real
    assert store.multiget(ids) == batch


# -------------------------------------------------- client-side group-commit
def test_extend_batcher_fuses_pending_writes():
    """Writes submitted while one RPC is in flight drain as ONE
    backend.extend; the id block splits back per caller."""
    import threading
    from concurrent.futures import ThreadPoolExecutor

    gate = threading.Event()
    entered = threading.Event()

    class SlowBackend:
        def __init__(self):
            self.calls = []
            self.n = 0

        def extend(self, strings):
            self.calls.append(len(strings))
            if len(self.calls) == 1:
                entered.set()
                assert gate.wait(5.0)
            ids = list(range(self.n, self.n + len(strings)))
            self.n += len(strings)
            return ids

    backend = SlowBackend()
    pool = ThreadPoolExecutor(max_workers=1)
    batcher = _ExtendBatcher(backend, pool.submit)
    first = batcher.submit_extend([b"a"])
    assert entered.wait(5.0)  # first drain is on the wire, holding the gate
    pending = [batcher.submit_extend([b"b", b"c"]),
               batcher.submit_extend([b"d"])]
    gate.set()
    assert first.result(5.0) == [0]
    assert pending[0].result(5.0) == [1, 2]
    assert pending[1].result(5.0) == [3]
    pool.shutdown(wait=True)
    assert backend.calls == [1, 3]  # second drain fused both pending writes
    assert batcher.batches == 2 and batcher.coalesced == 2


def test_client_async_appends_group_commit(artifact, titles, tmp_path):
    """Pipelined append_async/extend_async through a tcp:// client fold into
    bulk extends server-side (service append_batches < appends)."""
    src = str(tmp_path / "src")
    MutableStringStore(artifact).save(src)
    with ShardServer.from_dir(src) as server:
        server.start()
        with connect(f"tcp://127.0.0.1:{server.port}") as client:
            futs = [client.append_async(s) for s in titles[:48]]
            futs.append(client.extend_async(titles[48:64]))
            ids = [f.result(10.0) for f in futs]
            flat = ids[:48] + list(ids[48])
            assert sorted(flat) == list(range(64))
            got = client.multiget(flat)
            assert got == titles[:64]
            stats = client.stats()
            assert stats["extend_batches"] >= 1
            svc = server.service.stats()
            assert svc["appends"] == 64
            assert svc["append_batches"] <= 49


# ------------------------------------------- pallas/numpy lifecycle identity
@needs_jax
@pytest.mark.parametrize("transport", ["inproc", "tcp"])
def test_pallas_numpy_lifecycle_identity(artifact, titles, tmp_path,
                                         transport):
    """encode_backend='pallas' and 'numpy' stores produce byte-identical
    corpora through extend -> seal -> save -> open -> multiget, through the
    in-process client and over tcp://."""
    batch = titles[:300] + EDGE
    results = {}
    for backend in ("numpy", "pallas"):
        d = str(tmp_path / backend)
        store = MutableStringStore(artifact, encode_backend=backend,
                                   strings_per_segment=128)
        if transport == "inproc":
            with wrap(store) as client:
                ids = client.extend(batch)
        else:
            stage = str(tmp_path / f"{backend}-srv")
            store.save(stage)
            with ShardServer.from_dir(
                    stage, encode_backend=backend) as server:
                server.start()
                with connect(f"tcp://127.0.0.1:{server.port}") as client:
                    ids = client.extend(batch)
                server.store.save(stage)
            store = MutableStringStore.open(stage)
        store.seal()
        store.save(d)
        reopened = MutableStringStore.open(d)
        assert reopened.encode_backend == backend
        assert reopened.multiget(ids) == batch
        # byte-level identity of the stored token streams, not just decodes
        results[backend] = [reopened.corpus.string_payload(i)
                            for i in range(reopened.corpus.n_strings)]
    assert results["numpy"] == results["pallas"]


@needs_jax
def test_device_encode_matches_numpy_corpus(artifact, titles):
    batch = titles[:200] + EDGE
    assert Encoder(artifact, backend="pallas").encode(batch).payload.tobytes() \
        == Encoder(artifact).encode(batch).payload.tobytes()


# ------------------------------------------------------- jit retrace bound
@needs_jax
def test_encode_trace_count_bounded(artifact, titles):
    """Mixed batch sizes and string lengths must not compile a trace per
    (B, L) pair: encode_bucketed pins every launch to a static bucket
    shape, so compiled-trace growth is bounded by the bucket set."""
    from repro.kernels.ref import encode_batch_ref_jit

    device = OnPairDevice(registry.codec_from_artifact(artifact).dictionary)
    before = encode_batch_ref_jit._cache_size()
    rng = np.random.default_rng(0)
    for trial in range(12):
        n = int(rng.integers(1, 90))
        batch = [titles[int(rng.integers(len(titles)))][: int(rng.integers(1, 300))]
                 for _ in range(n)]
        device.encode_bucketed(batch, use_pallas=False)
    added = encode_batch_ref_jit._cache_size() - before
    assert added <= len(device.encode_len_caps), \
        f"{added} traces for {len(device.encode_len_caps)} buckets"
    pb = device.encode_pad_batch
    allowed = {(pb, cap + 16) for cap in device.encode_len_caps}
    assert device.encode_shapes <= allowed, \
        f"unexpected launch shapes {device.encode_shapes - allowed}"


@needs_jax
def test_warm_encode_precompiles_buckets(artifact):
    from repro.kernels.ref import encode_batch_ref_jit

    device = OnPairDevice(registry.codec_from_artifact(artifact).dictionary)
    device.warm_encode(use_pallas=False)
    before = encode_batch_ref_jit._cache_size()
    device.encode_bucketed([b"abc", b"x" * 100, b"y" * 500],
                           use_pallas=False)
    assert encode_batch_ref_jit._cache_size() == before  # all warm


if __name__ == "__main__":
    raise SystemExit(os.system(f"pytest -x -q {__file__}"))
