"""Per-kernel validation: Pallas (interpreted on the CPU) vs ref.py oracle vs
Python reference, swept over shapes/dtypes/corpora, plus hypothesis property
tests on the packing/compare primitives."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # optional dev dep: property tests skip, the rest run
    from _hypothesis_fallback import given, settings, st

from repro.core import make_onpair16
from repro.core.packed import hash_key as np_hash_key, split_u64
from repro.core.packing import pack_u64, shared_prefix_size
from repro.data.synth import load_dataset
from repro.kernels.ops import OnPairDevice
from repro.kernels.ref import hash_key, low_zero_bytes, shared_prefix_bytes

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")


@pytest.fixture(scope="module")
def trained():
    strings = load_dataset("book_titles", 1 << 19)
    comp = make_onpair16(sample_bytes=1 << 19, seed=7)
    comp.train(strings)
    return strings, comp


@pytest.fixture(scope="module")
def device(trained):
    _, comp = trained
    return OnPairDevice(comp.dictionary)


# ------------------------------------------------------------- primitives
@given(st.integers(0, 2**32 - 1))
@settings(max_examples=200, deadline=None)
def test_low_zero_bytes_matches_python(x):
    expected = 4 if x == 0 else ((x & -x).bit_length() - 1) // 8
    assert int(low_zero_bytes(jnp.uint32(x))) == expected


@given(st.binary(min_size=0, max_size=8), st.binary(min_size=0, max_size=8))
@settings(max_examples=200, deadline=None)
def test_shared_prefix_jax_vs_python(a, b):
    va, vb = pack_u64(a, 0, len(a)), pack_u64(b, 0, len(b))
    expect = min(shared_prefix_size(va, vb), 8)
    lo_a, hi_a = split_u64(va)
    lo_b, hi_b = split_u64(vb)
    got = int(shared_prefix_bytes(jnp.uint32(lo_a), jnp.uint32(hi_a),
                                  jnp.uint32(lo_b), jnp.uint32(hi_b)))
    assert got == expect


@given(st.integers(0, 2**64 - 1), st.integers(1, 16))
@settings(max_examples=200, deadline=None)
def test_hash_jax_matches_numpy(v, length):
    lo, hi = split_u64(v)
    assert int(hash_key(jnp.uint32(lo), jnp.uint32(hi), jnp.int32(length))) \
        == np_hash_key(lo, hi, length)


# ------------------------------------------------------------ encode kernel
@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("batch_size", [1, 7, 32])
def test_encode_matches_python_lpm(trained, device, use_pallas, batch_size):
    strings, comp = trained
    batch = strings[:batch_size]
    enc = device.encode_to_bytes(batch, use_pallas=use_pallas)
    for s, e in zip(batch, enc):
        assert e == comp.compress_string(s)


def test_encode_pallas_equals_ref_on_edge_strings(device):
    edge = [b"", b"a", b"ab", b"abcdefgh", b"abcdefghi", b"x" * 100,
            bytes(range(256)), b"\x00" * 20, b"abracadabra abracadabra"]
    # empty strings can't be packed (0 tokens) — encoder emits n=0
    toks_p, n_p = device.encode_batch(edge, use_pallas=True)
    toks_r, n_r = device.encode_batch(edge, use_pallas=False)
    np.testing.assert_array_equal(n_p, n_r)
    for i in range(len(edge)):
        np.testing.assert_array_equal(toks_p[i, : n_p[i]], toks_r[i, : n_r[i]])


# ------------------------------------------------------------ decode kernels
@pytest.mark.parametrize("use_pallas", [True, False])
def test_decode_roundtrip(trained, device, use_pallas):
    strings, _ = trained
    batch = strings[10:60]
    assert device.roundtrip(batch, use_pallas=use_pallas) == batch


@pytest.mark.parametrize("tile", [256, 1024])
def test_decode_stream_vs_python(trained, device, tile):
    strings, comp = trained
    batch = strings[:200]
    corpus = comp.compress(batch)
    tokens = np.asarray(corpus.payload.view("<u2"), dtype=np.int32)
    got = device.decode_stream(tokens, use_pallas=True, tile=tile)
    assert got == b"".join(batch)


def test_decode_gather_rows_match_dictionary(trained, device):
    _, comp = trained
    d = comp.dictionary
    rng = np.random.default_rng(0)
    toks = rng.integers(0, d.num_entries, size=2048).astype(np.int32)
    from repro.kernels.onpair_decode import decode_gather
    rows, lens = decode_gather(jnp.asarray(toks), device.dd.mat16,
                               device.dd.lens, tile=512)
    rows, lens = np.asarray(rows), np.asarray(lens)
    np.testing.assert_array_equal(rows, d.mat16[toks].astype(np.int32))
    np.testing.assert_array_equal(lens, d.lens[toks].astype(np.int32))


# ------------------------------------------------- property: full roundtrip
@given(st.lists(st.binary(min_size=0, max_size=64), min_size=1, max_size=8))
@settings(max_examples=30, deadline=None)
def test_property_roundtrip_arbitrary_bytes(trained, a_batch):
    """compress . decompress == identity for ARBITRARY byte strings, even
    ones unlike the training distribution (single-byte seeds guarantee it)."""
    _, comp = trained
    dev = OnPairDevice(comp.dictionary)
    batch = [s for s in a_batch]
    toks, n = dev.encode_batch(batch, use_pallas=False,
                               max_tokens=max(1, max(map(len, batch), default=1)))
    out = dev.decode_batch(toks, n, max_out=max(1, max(map(len, batch), default=1)),
                           use_pallas=False)
    assert out == batch


# --------------------------------------------------------- dtype/shape sweep
@pytest.mark.parametrize("length", [1, 8, 9, 16, 17, 63, 128])
def test_encode_shape_sweep(device, trained, length):
    _, comp = trained
    rng = np.random.default_rng(length)
    s = bytes(rng.integers(32, 127, size=length).astype(np.uint8))
    enc = device.encode_to_bytes([s], use_pallas=True)[0]
    assert enc == comp.compress_string(s)
    out = device.roundtrip([s], use_pallas=True)
    assert out == [s]


# ------------------------------------------------------------ compile cache
_CACHE_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
from repro.kernels.cache import CACHE_DIR, use_compile_cache
path = use_compile_cache()
configured = jax.config.jax_compilation_cache_dir
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(json.dumps({"path": path, "configured": configured,
                  "default": CACHE_DIR}))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and receives the entries; without it
    the cache is the checkout's fixed .jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "REPRO_NO_JAX")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    got = json.loads(out.stdout.strip().splitlines()[-1])
    want = str(tmp_path) if from_env else got["default"]
    assert got["path"] == got["configured"] == want
    assert os.path.basename(got["default"]) == ".jax_cache"
    if from_env:
        assert os.listdir(tmp_path), "no compiled entry was cached"
