"""Compile rehearsals: the Pallas kernels at real widths, compiled for one
chip of a described TPU v5e (2x2) topology.

Nothing runs: these prove the kernels lower through Mosaic at the shapes
the store serves (the 65536 x 16 dictionary, the (256, T) multiget buckets,
the (64, cap + 16) encode buckets), which interpret-mode tests on the CPU
cannot. The topology is described inside a fixture, never at import, so
every test worker collects the same tests and only the one running this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import make_onpair16
from repro.data.synth import load_dataset
from repro.kernels import onpair_decode, onpair_encode
from repro.kernels.ref import DeviceDict

#: the format's full dictionary width (core/onpair.py MAX_TOKENS)
N_ENTRIES = 65536


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [8, 16, 24])
def test_decode_compact_compiles_for_serving_bucket(one_chip, T):
    """The multiget kernel at the store's batch_size=256 with the urls
    corpus's quantile token caps, over a full-width dictionary."""
    _assert_kernel(onpair_decode.decode_compact.lower(
        _spec(one_chip, (256, T)), _spec(one_chip, (256,)),
        _spec(one_chip, (N_ENTRIES, 16)), _spec(one_chip, (N_ENTRIES,)),
        max_out=16 * T))


def test_decode_tokens_pallas_compiles_at_tile_1024(one_chip):
    T = 8 * 1024
    _assert_kernel(onpair_decode.decode_tokens_pallas.lower(
        _spec(one_chip, (T,)), _spec(one_chip, ()),
        _spec(one_chip, (N_ENTRIES, 16)), _spec(one_chip, (N_ENTRIES,)),
        max_out=16 * T, tile=1024))


@pytest.mark.parametrize("tile", [256, 1024])
def test_decode_gather_compiles_for_long_stream(one_chip, tile):
    """At ~110k tokens XLA tiles a 1-D SMEM operand by 1024, which Mosaic
    refuses for a smaller block: the token ids must travel 2-D."""
    T = 108 * 1024
    _assert_kernel(onpair_decode.decode_gather.lower(
        _spec(one_chip, (T,)), _spec(one_chip, (N_ENTRIES, 16)),
        _spec(one_chip, (N_ENTRIES,)), tile=tile))


@pytest.fixture(scope="module")
def small_dict(one_chip):
    strings = load_dataset("book_titles", 1 << 18, seed=0)
    comp = make_onpair16(sample_bytes=1 << 18, seed=0)
    comp.train(strings)
    return jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype),
        DeviceDict.build(comp.dictionary))


@pytest.mark.parametrize("cap", [32, 128, 512])
def test_encode_batch_pallas_compiles_for_encode_bucket(one_chip, small_dict,
                                                       cap):
    dd = small_dict
    _assert_kernel(onpair_encode.encode_batch_pallas.lower(
        _spec(one_chip, (64, cap + 16)), _spec(one_chip, (64,)), dd,
        max_tokens=cap))


#: rows of a full-width chained (unbounded OnPair) dictionary: about 1.5
#: rows an entry, as the URL column's dictionary has
N_CHAINED_ROWS = 2 * N_ENTRIES
#: the access_urls_onpair store's largest row bucket
ROW_CAP = 24


def test_decode_compact_compiles_for_chained_row_bucket(one_chip):
    """The multiget kernel over row streams of an unbounded OnPair store at
    its largest row bucket, with the continuation rows appended to the
    dictionary matrix."""
    _assert_kernel(onpair_decode.decode_compact.lower(
        _spec(one_chip, (256, ROW_CAP)), _spec(one_chip, (256,)),
        _spec(one_chip, (N_CHAINED_ROWS, 16)),
        _spec(one_chip, (N_CHAINED_ROWS,)), max_out=16 * ROW_CAP))


@pytest.mark.parametrize("T", [ROW_CAP, 64])
def test_decode_compact_compiles_at_the_dictionary_vmem_budget(one_chip, T):
    """The largest dictionary the device codec accepts (its packed table
    exactly ``DICT_VMEM_BYTES``) compiles: a dictionary that passes the
    check at open cannot fail to compile at its first decode."""
    rows = onpair_decode.DICT_VMEM_BYTES // (16 * 4)
    assert onpair_decode.packed_bytes(rows) == onpair_decode.DICT_VMEM_BYTES
    assert onpair_decode.packed_bytes(rows + 1) > onpair_decode.DICT_VMEM_BYTES
    _assert_kernel(onpair_decode.decode_compact.lower(
        _spec(one_chip, (256, T)), _spec(one_chip, (256,)),
        _spec(one_chip, (rows, 16)), _spec(one_chip, (rows,)),
        max_out=16 * T))
