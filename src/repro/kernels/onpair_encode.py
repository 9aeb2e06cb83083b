"""Pallas TPU kernel for OnPair16 parsing/compression (paper §3.3-3.4).

The static LPM structures (short-pattern hash table, prefix table, suffix
buckets — repro.core.packed) total well under VMEM capacity, so the whole
matcher state is VMEM-resident: each table is laid out as 128-lane rows,
and one element is read by loading its row and reducing the masked lane to
a scalar. The string bytes, the emitted tokens and the token counts live in
SMEM, where the scalar unit reads and writes them one at a time. Strings
are independent (the paper's random-access property), so the grid runs over
blocks of eight strings and a loop inside walks each of them.

The in-kernel search is literally repro.kernels.ref.lpm_search — the oracle
and the kernel share one implementation of Algorithm 1/2 and differ only in
how they read memory.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.onpair_decode import LANES, _round_up
from repro.kernels.platform import pallas_call
from repro.kernels.ref import DeviceDict, _pack_window, lpm_search

#: strings per grid step
_ROWS = 8
#: the LPM tables a kernel reads, in argument order
_TABLES = ("s_lo", "s_hi", "s_len", "s_tok", "p_lo", "p_hi", "p_len",
           "p_bucket", "bucket_start", "bucket_size", "suf_lo", "suf_hi",
           "suf_len", "suf_tok")


def _as_rows(x: jnp.ndarray) -> jnp.ndarray:
    """1-D table -> int32 (rows, 128), zero padded (Mosaic reduces no
    unsigned vectors, so u32 tables travel as their int32 bit patterns)."""
    n = x.shape[0]
    x = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.pad(x, (0, _round_up(max(n, 1), LANES) - n)).reshape(-1, LANES)


class _Table(NamedTuple):
    """A table's VMEM rows, with the 1-D shape and dtype the probe
    arithmetic sees."""

    ref: object
    shape: tuple
    dtype: object


def _encode_kernel(shapes, dtypes, s_probe_max, p_probe_max, max_bucket, data_ref,
                   len_ref, *refs):
    tables, (toks_ref, ntok_ref) = refs[:len(_TABLES)], refs[len(_TABLES):]
    dd = DeviceDict(mat16=None, lens=None,
                    **{name: _Table(*t) for name, t
                       in zip(_TABLES, zip(tables, shapes, dtypes))},
                    s_probe_max=s_probe_max, p_probe_max=p_probe_max,
                    max_bucket=max_bucket)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def at(table, i):
        i = i.astype(jnp.int32)
        row = table.ref[pl.ds(i >> 7, 1), :]
        return jnp.sum(jnp.where(lane == (i & (LANES - 1)), row, 0)
                       ).astype(table.dtype)

    max_tokens = toks_ref.shape[1]

    def clear(k, carry):
        toks_ref[k // max_tokens, k % max_tokens] = jnp.int32(0)
        return carry

    jax.lax.fori_loop(0, _ROWS * max_tokens, clear, 0)

    def encode_row(r, carry):
        def window(p):
            return _pack_window([data_ref[r, p + k] for k in range(8)])

        str_len = len_ref[r, 0]

        def cond(state):
            pos, count = state
            return (pos < str_len) & (count < max_tokens)

        def body(state):
            pos, count = state
            tok, mlen = lpm_search(window, at, pos, str_len, dd)
            toks_ref[r, count] = tok
            return pos + mlen, count + 1

        _, n = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(0)))
        ntok_ref[r, 0] = n
        return carry

    jax.lax.fori_loop(0, _ROWS, encode_row, 0)


@partial(jax.jit, static_argnames=("max_tokens",))
def encode_batch_pallas(data: jnp.ndarray, str_lens: jnp.ndarray,
                        dd: DeviceDict, max_tokens: int):
    """Compress a padded batch: data int32[B, L+16] (zero-padded byte values).

    Returns (tokens int32[B, max_tokens], n_tokens int32[B]).
    """
    B, Lp = data.shape
    Bp = _round_up(max(B, 1), _ROWS)
    data = jnp.pad(data, ((0, Bp - B), (0, 0)))
    lens = jnp.pad(str_lens, (0, Bp - B))[:, None]
    tables = [getattr(dd, name) for name in _TABLES]
    smem = partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    kernel = partial(_encode_kernel, [t.shape for t in tables],
                     [t.dtype for t in tables], dd.s_probe_max, dd.p_probe_max, dd.max_bucket)
    toks, n = pallas_call(
        kernel,
        grid=(Bp // _ROWS,),
        in_specs=[smem((_ROWS, Lp), lambda i: (i, 0)),
                  smem((_ROWS, 1), lambda i: (i, 0))]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(tables),
        out_specs=[smem((_ROWS, max_tokens), lambda i: (i, 0)),
                   smem((_ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Bp, max_tokens), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, 1), jnp.int32)],
    )(data, lens, *[_as_rows(t) for t in tables])
    return toks[:B], n[:B, 0]
