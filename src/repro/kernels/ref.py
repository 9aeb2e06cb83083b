"""Pure-jnp oracles for the OnPair kernels (DESIGN.md §3).

These are the reference semantics the Pallas kernels are validated against,
and double as the jittable batch encode/decode used on the host/CPU path.

Byte convention: JAX-side "bytes" are int32 arrays of values 0..255 (default
JAX has no u64 and TPU u8 compute is awkward; packing happens in u32 pairs,
exactly mirroring repro.core.packed). All hashes are bit-identical to
repro.core.packed.mix32 / hash_key.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packed import PackedDictionary


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3-style finaliser; must match repro.core.packed.mix32."""
    x = x.astype(jnp.uint32)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def hash_key(lo: jnp.ndarray, hi: jnp.ndarray, length: jnp.ndarray) -> jnp.ndarray:
    return mix32(lo ^ mix32(hi ^ mix32(length.astype(jnp.uint32))))


def low_zero_bytes(d: jnp.ndarray) -> jnp.ndarray:
    """Zero low-order bytes of u32 ``d`` (4 if d == 0), by byte-mask
    compares: the scalar unit of a TPU core has no population count."""
    d = d.astype(jnp.uint32)
    return sum(((d & jnp.uint32(m)) == 0).astype(jnp.int32)
               for m in (0xFF, 0xFFFF, 0xFFFFFF, 0xFFFFFFFF))


def shared_prefix_bytes(lo1, hi1, lo2, hi2) -> jnp.ndarray:
    """Algorithm 2 on (lo, hi) u32 pairs: # of matching low-order bytes."""
    dlo = (lo1 ^ lo2).astype(jnp.uint32)
    dhi = (hi1 ^ hi2).astype(jnp.uint32)
    return jnp.where(dlo != 0, low_zero_bytes(dlo),
                     4 + low_zero_bytes(dhi)).astype(jnp.int32)


@dataclass(frozen=True)
class DeviceDict:
    """PackedDictionary uploaded as device arrays (static LPM + decode).

    The decode matrix holds the dictionary as chained 16-byte rows
    (:meth:`PackedDictionary.chained_rows`): row ``t`` is entry ``t``'s
    first 16 bytes, and an entry longer than 16 B continues in rows
    appended after the ``N`` first ones (none for OnPair16). Where each
    chain runs stays on the host (``PackedDictionary.row_count``,
    ``row_next``), which expands token streams into row streams.
    """

    # decode
    mat16: jnp.ndarray       # int32[N + X, 16]   byte values
    lens: jnp.ndarray        # int32[N + X]       bytes of each row
    # short tier
    s_lo: jnp.ndarray        # uint32[S]
    s_hi: jnp.ndarray
    s_len: jnp.ndarray       # int32[S] (0 = empty)
    s_tok: jnp.ndarray       # int32[S]
    # long tier
    p_lo: jnp.ndarray        # uint32[P]
    p_hi: jnp.ndarray
    p_len: jnp.ndarray       # int32[P] (0 = empty, 8 = occupied)
    p_bucket: jnp.ndarray    # int32[P]
    bucket_start: jnp.ndarray
    bucket_size: jnp.ndarray
    suf_lo: jnp.ndarray      # uint32[M]
    suf_hi: jnp.ndarray
    suf_len: jnp.ndarray     # int32[M]
    suf_tok: jnp.ndarray     # int32[M]
    # static probe bounds / sizes (python ints -> static under jit)
    s_probe_max: int
    p_probe_max: int
    max_bucket: int

    @staticmethod
    def build(d: PackedDictionary, device=None) -> "DeviceDict":
        """Upload ``d``'s tables to ``device`` (JAX's default when None)."""
        def put(x):
            return jax.device_put(np.asarray(x), device)

        rows, row_lens = d.chained_rows()
        return DeviceDict(
            mat16=put(rows.astype(np.int32)),
            lens=put(row_lens.astype(np.int32)),
            s_lo=put(d.s_lo), s_hi=put(d.s_hi),
            s_len=put(d.s_len), s_tok=put(d.s_tok),
            p_lo=put(d.p_lo), p_hi=put(d.p_hi),
            p_len=put(d.p_len), p_bucket=put(d.p_bucket),
            bucket_start=put(d.bucket_start),
            bucket_size=put(d.bucket_size),
            suf_lo=put(d.suf_lo), suf_hi=put(d.suf_hi),
            suf_len=put(d.suf_len), suf_tok=put(d.suf_tok),
            s_probe_max=int(d.s_probe_max), p_probe_max=int(d.p_probe_max),
            max_bucket=int(max(1, d.max_bucket_size)),
        )


jax.tree_util.register_pytree_node(
    DeviceDict,
    lambda d: ((d.mat16, d.lens, d.s_lo, d.s_hi, d.s_len, d.s_tok,
                d.p_lo, d.p_hi, d.p_len, d.p_bucket, d.bucket_start,
                d.bucket_size, d.suf_lo, d.suf_hi, d.suf_len, d.suf_tok),
               (d.s_probe_max, d.p_probe_max, d.max_bucket)),
    lambda aux, ch: DeviceDict(*ch, s_probe_max=aux[0], p_probe_max=aux[1],
                               max_bucket=aux[2]),
)


# ============================================================ decode oracle
def decode_ref(tokens: jnp.ndarray, n_tokens: jnp.ndarray,
               mat16: jnp.ndarray, lens: jnp.ndarray,
               max_out: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two-phase TPU-native decode of one token stream.

    Phase 1: gather fixed 16-byte rows + lengths (the paper's fixed-size-copy
    insight as a dense gather). Phase 2: exclusive prefix-sum of lengths and
    a masked scatter to compact the ragged rows into a byte stream.

    Returns (out bytes int32[max_out], out_len int32).
    """
    T = tokens.shape[0]
    valid = jnp.arange(T, dtype=jnp.int32) < n_tokens
    tl = jnp.where(valid, lens[tokens], 0).astype(jnp.int32)
    ends = jnp.cumsum(tl)
    starts = ends - tl
    out_len = ends[-1] if T > 0 else jnp.int32(0)
    rows = mat16[tokens]                                   # (T, 16)
    j = jnp.arange(16, dtype=jnp.int32)
    idx = starts[:, None] + j[None, :]
    mask = (j[None, :] < tl[:, None]) & valid[:, None]
    idx_safe = jnp.where(mask, idx, max_out)               # dump lane
    out = jnp.zeros(max_out + 1, dtype=jnp.int32)
    out = out.at[idx_safe.reshape(-1)].set(rows.reshape(-1), mode="drop")
    return out[:max_out], out_len


def decode_batch_ref(tokens: jnp.ndarray, n_tokens: jnp.ndarray,
                     mat16: jnp.ndarray, lens: jnp.ndarray,
                     max_out: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """vmap of decode_ref over a batch: tokens int32[B, T]."""
    return jax.vmap(decode_ref, in_axes=(0, 0, None, None, None))(
        tokens, n_tokens, mat16, lens, max_out)


# ============================================================ encode oracle
def _pack_window(window) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pack 8 byte-values (an int32[8], or eight int32 scalars) little-endian
    into (lo, hi) u32."""
    w = [window[k].astype(jnp.uint32) for k in range(8)]
    lo = w[0] | (w[1] << 8) | (w[2] << 16) | (w[3] << 24)
    hi = w[4] | (w[5] << 8) | (w[6] << 16) | (w[7] << 24)
    return lo, hi


def _probe_table(at, lo, hi, length, t_lo, t_hi, t_len, t_payload,
                 probe_max: int):
    """Linear-probe an open-addressing table; returns payload or -1.

    Probing stops at the first empty slot (len == 0) — matching insertion —
    and is bounded by the build-time max probe count, so the loop is static.
    ``at(table, i)`` reads one table element (see :func:`lpm_search`).
    """
    size = t_lo.shape[0]
    mask = jnp.uint32(size - 1)
    slot0 = hash_key(lo, hi, length) & mask

    def body(i, carry):
        found, done = carry
        slot = ((slot0 + i.astype(jnp.uint32)) & mask).astype(jnp.int32)
        sl = at(t_len, slot)
        hit = (sl == length) & (at(t_lo, slot) == lo) & (at(t_hi, slot) == hi)
        empty = sl == 0
        found = jnp.where(~done & hit, at(t_payload, slot), found)
        done = done | hit | empty
        return found, done

    found, _ = jax.lax.fori_loop(
        0, probe_max, body, (jnp.int32(-1), jnp.bool_(False)))
    return found


def lpm_search(window, at, pos: jnp.ndarray, str_len: jnp.ndarray,
               dd: DeviceDict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Algorithm 1 at one position: (token_id, match_len).

    The oracle and the encode kernel share this walk and differ only in
    how they read memory: ``window(p)`` packs the string's 8 bytes at ``p``
    into (lo, hi) u32, and ``at(table, i)`` reads element ``i`` of one of
    ``dd``'s LPM tables. Requires all 256 single bytes present.
    """
    rem = str_len - pos
    lo1, hi1 = window(pos)

    # ---- long tier ----
    lo2, hi2 = window(pos + 8)
    bucket = _probe_table(at, lo1, hi1, jnp.int32(8), dd.p_lo, dd.p_hi,
                          dd.p_len, dd.p_bucket, dd.p_probe_max)
    use_long = (rem > 8) & (bucket >= 0)
    b = jnp.maximum(bucket, 0)
    start = at(dd.bucket_start, b)
    size = jnp.where(use_long, at(dd.bucket_size, b), 0)

    def bucket_body(k, carry):
        tok, mlen, done = carry
        i = start + k
        in_range = k < size
        s_len = at(dd.suf_len, i)
        fits = s_len <= (rem - 8)
        shared = shared_prefix_bytes(lo2, hi2, at(dd.suf_lo, i),
                                     at(dd.suf_hi, i))
        # OnPair16: suffixes are <= 8 B so the packed compare is exact.
        hit = in_range & fits & (shared >= s_len) & ~done
        tok = jnp.where(hit, at(dd.suf_tok, i), tok)
        mlen = jnp.where(hit, 8 + s_len, mlen)
        done = done | hit | ~in_range
        return tok, mlen, done

    ltok, lmlen, _ = jax.lax.fori_loop(
        0, dd.max_bucket, bucket_body,
        (jnp.int32(-1), jnp.int32(0), jnp.bool_(False)))
    long_found = use_long & (ltok >= 0)

    # ---- short tier: lengths min(rem, 8) .. 1 ----
    max_len = jnp.minimum(rem, 8).astype(jnp.int32)

    def byte_mask(nbytes):
        """uint32 mask covering the low min(nbytes, 4) bytes (0 if <= 0)."""
        nb = jnp.clip(nbytes, 0, 4).astype(jnp.uint32)
        return jnp.where(nb >= 4, jnp.uint32(0xFFFFFFFF),
                         (jnp.uint32(1) << (nb * 8)) - jnp.uint32(1))

    def short_body(i, carry):
        tok, mlen, done = carry
        length = max_len - i
        ok = length >= 1
        lo = lo1 & byte_mask(length)
        hi = hi1 & byte_mask(length - 4)
        cand = _probe_table(at, lo, hi, length, dd.s_lo, dd.s_hi, dd.s_len,
                            dd.s_tok, dd.s_probe_max)
        hit = ok & (cand >= 0) & ~done
        tok = jnp.where(hit, cand, tok)
        mlen = jnp.where(hit, length, mlen)
        done = done | hit
        return tok, mlen, done

    stok, smlen, _ = jax.lax.fori_loop(
        0, 8, short_body, (jnp.int32(0), jnp.int32(1), jnp.bool_(False)))

    tok = jnp.where(long_found, ltok, stok)
    mlen = jnp.where(long_found, lmlen, smlen)
    return tok.astype(jnp.int32), mlen.astype(jnp.int32)


def _lpm_search_ref(data_row: jnp.ndarray, pos: jnp.ndarray,
                    str_len: jnp.ndarray,
                    dd: DeviceDict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`lpm_search` over arrays; data_row is int32[L+16] zero-padded."""
    def window(p):
        return _pack_window(jax.lax.dynamic_slice(data_row, (p,), (8,)))

    return lpm_search(window, lambda t, i: t[i], pos, str_len, dd)


def encode_ref(data_row: jnp.ndarray, str_len: jnp.ndarray,
               dd: DeviceDict, max_tokens: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy LPM parse of one string (paper §3.3) as a lax.while_loop.

    data_row: int32[L+16] zero-padded byte values. Returns
    (tokens int32[max_tokens], n_tokens int32).
    """
    tokens0 = jnp.zeros(max_tokens, dtype=jnp.int32)

    def cond(state):
        pos, count, _ = state
        return (pos < str_len) & (count < max_tokens)

    def body(state):
        pos, count, toks = state
        tok, mlen = _lpm_search_ref(data_row, pos, str_len, dd)
        toks = toks.at[count].set(tok)
        return pos + mlen, count + 1, toks

    _, n, toks = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(0), tokens0))
    return toks, n


def encode_batch_ref(data: jnp.ndarray, str_lens: jnp.ndarray,
                     dd: DeviceDict, max_tokens: int):
    """vmap of encode_ref: data int32[B, L+16]."""
    return jax.vmap(encode_ref, in_axes=(0, 0, None, None))(
        data, str_lens, dd, max_tokens)


# ============================================================ jit wrappers
@partial(jax.jit, static_argnames=("max_out",))
def decode_batch_ref_jit(tokens, n_tokens, mat16, lens, max_out: int):
    """decode_batch_ref with its bytes as uint8, as ``decode_compact``
    returns them."""
    out, out_len = decode_batch_ref(tokens, n_tokens, mat16, lens, max_out)
    return out.astype(jnp.uint8), out_len


@partial(jax.jit, static_argnames=("max_tokens",))
def encode_batch_ref_jit(data, str_lens, dd: DeviceDict, max_tokens: int):
    return encode_batch_ref(data, str_lens, dd, max_tokens)
