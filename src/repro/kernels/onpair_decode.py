"""Pallas TPU kernels for OnPair16 decompression (paper §3.5, Algorithm 3).

TPU adaptation (DESIGN.md §3): the whole OnPair16 dictionary is held in
VMEM, so decode is a *VMEM-resident gather*. The (N, 16) byte matrix is
packed eight entries to a 128-lane row (:func:`pack_rows`), so the full
65536-entry dictionary takes 4 MiB of VMEM as int32 instead of the 32 MiB a
16-lane-wide array pads out to. Entry ``tok`` sits on row ``tok >> 3``,
lanes ``16 * (tok & 7)`` onward; a kernel loads that row with one dynamic
sublane read and moves the 16 bytes into place with one lane rotation.

Two kernels:

* ``decode_gather``  — throughput variant: grid over token tiles; each tile
  gathers its fixed 16-byte rows. The ragged compaction (exclusive
  prefix-sum + masked scatter) happens outside in jnp, mirroring the paper's
  two-stage "copy 16 unconditionally, fix up after" split.
* ``decode_compact`` — latency variant (random access): grid over blocks of
  eight strings; a sequential loop per string performs Algorithm 3 —
  unconditional fixed-size 16-byte store at the output cursor. The cursor
  of every token (exclusive prefix-sum of the token lengths) is computed in
  jnp before the kernel and read from SMEM with the token ids.

Both run compiled on a TPU and interpreted on the CPU
(:mod:`repro.kernels.platform`); tests check them against repro.kernels.ref
and the host decoder, and compile them for a described v5e chip.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

#: lanes of one vector register row; the packed dictionary's row width
LANES = 128
#: strings per decode_compact grid step (one sublane tile)
_ROWS = 8
#: VMEM the packed dictionary may take: half of a v5e core's 128 MiB, the
#: rest left to the output blocks. A table of exactly this size compiles for
#: v5e at (256, 24) and (256, 64) row buckets; one of 128 MiB runs out of
#: VMEM (tests/test_tpu_compile.py)
DICT_VMEM_BYTES = 64 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pack_rows(mat16: jnp.ndarray) -> jnp.ndarray:
    """(N, 16) dictionary bytes -> (ceil(N / 8), 128): eight entries a row."""
    n = mat16.shape[0]
    pad = _round_up(max(n, 1), 8) - n
    return jnp.pad(mat16, ((0, pad), (0, 0))).reshape(-1, LANES)


def packed_bytes(rows: int) -> int:
    """VMEM bytes of a ``(rows, 16)`` dictionary packed by :func:`pack_rows`
    (int32 byte values, eight rows to a 128-lane row)."""
    return _round_up(max(rows, 1), 8) * 16 * 4


def _entry_row(dict_ref, tok):
    """The packed row holding entry ``tok`` (one dynamic sublane load)."""
    return dict_ref[pl.ds(tok >> 3, 1), :]


def _vmem():
    return pl.BlockSpec(memory_space=pltpu.VMEM)


# ------------------------------------------------------------- gather kernel
def _gather_kernel(tok_ref, dict_ref, out_ref):
    """Token ``tok_ref[i, k]`` -> output row ``i``, lanes ``16 * k``
    onward: the (tile, 16) result, eight tokens a row."""
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) >> 4

    def row(i, carry):
        acc = jnp.zeros((1, LANES), jnp.int32)
        for k in range(8):
            tok = tok_ref[i, k]
            moved = pltpu.roll(_entry_row(dict_ref, tok),
                               (16 * k - 16 * (tok & 7)) & (LANES - 1), 1)
            acc = jnp.where(slot == k, moved, acc)
        out_ref[pl.ds(i, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, tok_ref.shape[0], row, 0)


@partial(jax.jit, static_argnames=("tile",))
def decode_gather(tokens: jnp.ndarray, mat16: jnp.ndarray, lens: jnp.ndarray,
                  tile: int = 1024):
    """Phase-1 decode: tokens int32[T] -> (rows int32[T,16], lens int32[T]).

    T must be a multiple of ``tile`` and ``tile`` a multiple of 64 (pad
    tokens with 0; the padding rows are masked out by the caller's
    prefix-sum phase).
    """
    T = tokens.shape[0]
    assert T % tile == 0, "pad the token stream to a tile multiple"
    assert tile % 64 == 0, "tile must be a multiple of 64"
    # token ids travel eight a row, as 2-D SMEM blocks: a long 1-D SMEM
    # operand gets an XLA tiling Mosaic does not accept
    rows = pallas_call(
        _gather_kernel,
        grid=(T // tile,),
        in_specs=[
            pl.BlockSpec((tile // 8, 8), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            _vmem(),
        ],
        out_specs=pl.BlockSpec((tile // 8, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T // 8, LANES), jnp.int32),
    )(tokens.reshape(T // 8, 8), pack_rows(mat16))
    return rows.reshape(T, 16), lens[tokens]


@partial(jax.jit, static_argnames=("max_out", "tile"))
def decode_tokens_pallas(tokens: jnp.ndarray, n_tokens: jnp.ndarray,
                         mat16: jnp.ndarray, lens: jnp.ndarray,
                         max_out: int, tile: int = 1024):
    """Full two-phase decode of one padded token stream.

    Phase 1 = Pallas gather kernel; phase 2 = prefix-sum + masked scatter
    (pure jnp — XLA fuses it; on TPU this is the vector-unit-friendly
    replacement for sequential output appends).
    """
    T = tokens.shape[0]
    rows, tl = decode_gather(tokens, mat16, lens, tile=tile)
    valid = jnp.arange(T, dtype=jnp.int32) < n_tokens
    tl = jnp.where(valid, tl, 0)
    ends = jnp.cumsum(tl)
    starts = ends - tl
    out_len = ends[-1] if T > 0 else jnp.int32(0)
    j = jnp.arange(16, dtype=jnp.int32)
    idx = starts[:, None] + j[None, :]
    mask = (j[None, :] < tl[:, None]) & valid[:, None]
    idx_safe = jnp.where(mask, idx, max_out)
    out = jnp.zeros(max_out + 1, dtype=jnp.int32)
    out = out.at[idx_safe.reshape(-1)].set(rows.reshape(-1), mode="drop")
    return out[:max_out], out_len


# ------------------------------------------------------------ compact kernel
def _compact_kernel(tok_ref, start_ref, n_ref, dict_ref, out_ref):
    """Algorithm 3 per string: fixed 16-byte store at the token's cursor.

    Each store covers lanes ``pos .. pos + 15`` of the string's output row;
    they lie in the 128-lane window holding ``pos`` and may run on into the
    next one. Later tokens overwrite the zero tail of earlier ones, exactly
    as the unconditional copy of the paper does.
    """
    out_ref[...] = jnp.zeros_like(out_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def string(r, carry):
        def store(t, carry):
            tok = tok_ref[r, t]
            pos = start_ref[r, t]
            off = pos & (LANES - 1)
            moved = pltpu.roll(_entry_row(dict_ref, tok),
                               (off - 16 * (tok & 7)) & (LANES - 1), 1)
            base = pl.multiple_of(pos - off, LANES)
            here = out_ref[r, :, pl.ds(base, LANES)]
            out_ref[r, :, pl.ds(base, LANES)] = jnp.where(
                (lane >= off) & (lane < off + 16), moved, here)
            nxt = pl.multiple_of(base + LANES, LANES)
            spill = out_ref[r, :, pl.ds(nxt, LANES)]
            out_ref[r, :, pl.ds(nxt, LANES)] = jnp.where(
                lane < off + 16 - LANES, moved, spill)
            return carry

        return jax.lax.fori_loop(0, n_ref[r, 0], store, carry)

    jax.lax.fori_loop(0, _ROWS, string, 0)


@partial(jax.jit, static_argnames=("max_out",))
def decode_compact(tokens: jnp.ndarray, n_tokens: jnp.ndarray,
                   mat16: jnp.ndarray, lens: jnp.ndarray, max_out: int):
    """Per-string sequential decode: tokens int32[B,T] -> (out int32[B,max_out],
    out_len int32[B]). Grid = blocks of eight strings (each string decodes
    independently — the paper's random-access property is the parallelism
    axis). Bytes past ``max_out`` are not written."""
    B, T = tokens.shape
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < n_tokens[:, None]
    tl = jnp.where(valid, lens[tokens], 0)
    ends = jnp.cumsum(tl, axis=1)
    starts = ends - tl
    olen = ends[:, -1] if T else jnp.zeros(B, jnp.int32)
    # cursors grow along a row, so the tokens that start inside the output
    # are a prefix: the kernel runs that many
    n_run = jnp.sum(valid & (starts < max_out), axis=1, dtype=jnp.int32)
    Bp = _round_up(max(B, 1), _ROWS)
    W = _round_up(max_out, LANES) + LANES   # room for the last 16-byte spill

    def pad(x):
        return jnp.pad(x, ((0, Bp - B), (0, 0)))

    smem = partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pallas_call(
        _compact_kernel,
        grid=(Bp // _ROWS,),
        in_specs=[
            smem((_ROWS, T), lambda i: (i, 0)),
            smem((_ROWS, T), lambda i: (i, 0)),
            smem((_ROWS, 1), lambda i: (i, 0)),
            _vmem(),
        ],
        # one (1, W) slab per string: Mosaic indexes a leading dimension
        # dynamically, so the kernel loops over the strings of a block
        # (a dynamic sublane of a 2-D window is refused)
        out_specs=pl.BlockSpec((_ROWS, 1, W), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1, W), jnp.int32),
    )(pad(tokens), pad(starts), pad(n_run[:, None]), pack_rows(mat16))
    return out[:B, 0, :max_out], olen
