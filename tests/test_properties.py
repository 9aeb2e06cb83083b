"""Property-based round-trip suite for every registered codec.

Arbitrary byte strings — empty, 1-byte, >16-byte, high-byte, UTF-8
fragments — must round-trip through each codec's train→encode→decode and
through the stateless ``Encoder``/``Decoder`` API; numpy and pallas
backends must agree wherever the registry says ``device_decodable``; and
the writable store must return appended strings byte-identically.

Runs under hypothesis when installed; without it the ``@given`` tests skip
(via ``_hypothesis_fallback``) while the concrete edge-case tests below
still execute, so the numpy-only minimal-deps CI job keeps covering the
same codecs with a fixed adversarial corpus.
"""

from functools import lru_cache

import numpy as np
import pytest

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dev dep: property tests skip, the rest run
    from _hypothesis_fallback import example, given, settings, st
    HAVE_HYPOTHESIS = False

try:
    import jax  # noqa: F401
    HAVE_JAX = True
except Exception:
    HAVE_JAX = False

from repro.core import registry
from repro.core.codec import Decoder, Encoder
from repro.data.synth import load_dataset
from repro.store import MutableStringStore

SAMPLE = 1 << 16  # small training corpus keeps per-example rebuilds cheap

#: fixed adversarial strings: empty, 1-byte, >16-byte (longer than any
#: bounded dictionary entry), high bytes, UTF-8 + truncated UTF-8 fragments
EDGE_CASES = [
    b"",
    b"\x00",
    b"\xff",
    b"a",
    bytes(range(256)),
    "héllo wörld".encode("utf-8"),
    "日本語のテキスト".encode("utf-8"),
    "héllo".encode("utf-8")[:3],      # truncated multi-byte sequence
    b"\xf0\x9f\x92",                   # dangling emoji prefix
    b"x" * 17,
    b"ab" * 100,
    b"\x00" * 33,
    b"\xfe\xff" * 21,
]

if HAVE_HYPOTHESIS:
    ARBITRARY = st.one_of(
        st.binary(min_size=0, max_size=48),
        st.binary(min_size=17, max_size=160),            # > 16-byte entries
        st.text(max_size=40).map(lambda t: t.encode()),  # valid UTF-8
        st.sampled_from(EDGE_CASES),
    )
    BATCH = st.lists(ARBITRARY, min_size=0, max_size=8)
else:  # fallback: strategies are never drawn, placeholders suffice
    ARBITRARY = BATCH = None


@lru_cache(maxsize=None)
def _artifact(name: str):
    corpus = load_dataset("book_titles", SAMPLE)
    if registry.capabilities(name).trainable:
        return registry.train(name, corpus, sample_bytes=SAMPLE)
    return registry.create(name).to_artifact()


@lru_cache(maxsize=None)
def _coders(name: str):
    art = _artifact(name)
    return Encoder(art), Decoder(art)


@lru_cache(maxsize=None)
def _pallas_decoder(name: str):
    return Decoder(_artifact(name), backend="pallas")


def _check_roundtrip(name: str, strings: list) -> None:
    enc, dec = _coders(name)
    corpus = enc.encode(strings)
    if "str_block" not in corpus.meta:  # block layouts index blocks, not strings
        assert corpus.n_strings == len(strings)
    assert dec.decode_all(corpus) == b"".join(strings), name
    for i, s in enumerate(strings):
        assert dec.access(corpus, i) == s, (name, i)


# ---------------------------------------------------------------- properties
@given(strings=BATCH)
@settings(max_examples=25, deadline=None)
def test_roundtrip_every_codec(strings):
    for name in registry.names():
        _check_roundtrip(name, strings)


@given(s=ARBITRARY)
@settings(max_examples=50, deadline=None)
def test_encode_one_and_access(s):
    """Encoder.encode_one emits exactly the per-string payload, and that
    payload decodes alone through the frozen dictionary (token codecs)."""
    for name in registry.names():
        enc, dec = _coders(name)
        corpus = enc.encode([b"padding", s, b"more padding"])
        assert dec.access(corpus, 1) == s, name
        if registry.capabilities(name).token_stream:
            payload = enc.encode_one(s)
            assert payload == corpus.string_payload(1), name
            toks = np.frombuffer(payload, dtype="<u2").astype(np.int64)
            assert dec.dictionary.decode_tokens(toks) == s, name


@pytest.mark.skipif(not HAVE_JAX, reason="jax unavailable")
@given(strings=BATCH)
@example(strings=[])  # an empty multiget once reached the kernel
@settings(max_examples=10, deadline=None)
def test_numpy_pallas_backend_equivalence(strings):
    for name in registry.names():
        if not registry.capabilities(name).device_decodable:
            continue
        enc, host = _coders(name)
        dev = _pallas_decoder(name)
        corpus = enc.encode(strings)
        ids = list(range(len(strings)))
        assert dev.multiget(corpus, ids) == host.multiget(corpus, ids), name
        assert dev.decode_all(corpus) == host.decode_all(corpus), name


@given(strings=BATCH)
@settings(max_examples=10, deadline=None)
def test_mutable_store_append_roundtrip(strings):
    """Appending arbitrary strings against a frozen dictionary and reading
    them back through every store path is the identity."""
    store = MutableStringStore(_artifact("onpair16"),
                               strings_per_segment=4, cache_bytes=0,
                               backend="numpy")
    ids = store.extend(strings)
    assert ids == list(range(len(strings)))
    assert store.multiget(ids) == strings
    assert store.scan(0, len(strings)) == strings


# ------------------------------------------- concrete edge-case regressions
# (run everywhere, including the numpy-only job without hypothesis)
@pytest.mark.parametrize("name", registry.names())
def test_edge_cases_roundtrip(name):
    _check_roundtrip(name, EDGE_CASES)


@pytest.mark.parametrize("name", registry.names())
def test_empty_corpus_roundtrip(name):
    _check_roundtrip(name, [])
    _check_roundtrip(name, [b"", b"", b""])


def test_edge_cases_through_mutable_store():
    store = MutableStringStore(_artifact("onpair16"),
                               strings_per_segment=4, cache_bytes=0)
    ids = store.extend(EDGE_CASES)
    assert store.multiget(ids) == EDGE_CASES
    assert store.scan(0, len(EDGE_CASES)) == EDGE_CASES


@pytest.mark.skipif(not HAVE_JAX, reason="jax unavailable")
def test_edge_cases_backend_equivalence():
    for name in registry.names():
        if not registry.capabilities(name).device_decodable:
            continue
        enc, host = _coders(name)
        dev = _pallas_decoder(name)
        corpus = enc.encode(EDGE_CASES)
        ids = list(range(len(EDGE_CASES)))
        assert dev.multiget(corpus, ids) == host.multiget(corpus, ids), name
