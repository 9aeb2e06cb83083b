"""Public jit'd entry points over the OnPair kernels.

Bridges host-side types (PackedDictionary, list[bytes]) to the padded device
layouts the kernels consume. Used by the serving path (on-device
detokenisation) and by the benchmark harness; tests validate every path
against repro.kernels.ref and the Python reference implementations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packed import PackedDictionary
from repro.kernels import onpair_decode, onpair_encode
from repro.kernels.ref import (DeviceDict, decode_batch_ref_jit,
                               encode_batch_ref_jit)
from repro.obs import REGISTRY, TRACER, Counter

#: device decode invocations by kernel path — pallas vs the jitted reference
_DECODE_BATCHES = {
    path: REGISTRY.register(Counter("repro_kernel_decode_batches_total",
                                    labels={"path": path}))
    for path in ("pallas", "ref")
}


def _profiler_annotation(name: str):
    """The tracer's hook in a process with JAX: while a profile is being
    taken, each span is also a profiler annotation of the same name, so
    the spans sit beside the device's operations in the trace."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name)
    return None


TRACER.annotate_with(_profiler_annotation)


def _pad_to(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


#: geometric byte-length bucket capacities seeding the bucketed encode path;
#: grown by doubling when a longer string arrives, so the set of compiled
#: encode shapes stays bounded no matter the batch mix
_ENCODE_LEN_BUCKETS = (32, 128, 512)
#: static batch dimension of every bucketed encode launch
_ENCODE_PAD_BATCH = 64


def pack_strings(strings: list[bytes], pad_len: int | None = None,
                 pad_extra: int = 16) -> tuple[np.ndarray, np.ndarray]:
    """list[bytes] -> (data int32[B, L+pad_extra], lens int32[B])."""
    L = pad_len if pad_len is not None else max((len(s) for s in strings), default=1)
    data = np.zeros((len(strings), L + pad_extra), dtype=np.int32)
    lens = np.zeros(len(strings), dtype=np.int32)
    for i, s in enumerate(strings):
        b = np.frombuffer(s, dtype=np.uint8)
        data[i, : len(b)] = b
        lens[i] = len(b)
    return data, lens


def pack_token_matrix(token_lists: list[np.ndarray], pad_tokens: int | None = None,
                      pad_batch: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Ragged token streams -> padded (tokens int32[B, T], n_tokens int32[B]).

    The multiget assembly step: ``pad_tokens``/``pad_batch`` pin T and B so a
    serving layer can keep the set of jit-compiled decode shapes small and
    static (length-bucketed batches). Padding rows/tails are zeros with
    n_tokens masking them out.
    """
    B = pad_batch if pad_batch is not None else len(token_lists)
    if B < len(token_lists):
        raise ValueError(f"pad_batch={B} < batch of {len(token_lists)}")
    T = pad_tokens if pad_tokens is not None else max(
        (len(t) for t in token_lists), default=1)
    T = max(T, 1)
    tokens = np.zeros((B, T), dtype=np.int32)
    n_tokens = np.zeros(B, dtype=np.int32)
    for i, t in enumerate(token_lists):
        if len(t) > T:
            raise ValueError(f"stream {i} has {len(t)} tokens > pad_tokens={T}")
        tokens[i, : len(t)] = t
        n_tokens[i] = len(t)
    return tokens, n_tokens


class OnPairDevice:
    """Device-side OnPair16 codec over a trained PackedDictionary."""

    def __init__(self, dictionary: PackedDictionary, device=None):
        if not dictionary.variant16:
            raise ValueError("device kernels target OnPair16 (<=16B entries); "
                             "unbounded OnPair stays on the host path")
        self.dictionary = dictionary
        # the tables live on ``device`` (JAX's default when None); every
        # kernel call runs where they are
        self.dd = DeviceDict.build(dictionary, device)
        # Bucketed-encode state: every launch uses a static
        # (encode_pad_batch, cap + 16) shape drawn from encode_len_caps, so
        # the number of compiled encode traces is bounded by the bucket set
        # rather than by the batch mix (mirrors the multiget_decode buckets).
        self.encode_len_caps: list[int] = list(_ENCODE_LEN_BUCKETS)
        self.encode_pad_batch: int = _ENCODE_PAD_BATCH
        #: every (B, L) data shape handed to the encode kernels — tests assert
        #: this stays bounded under mixed-length workloads
        self.encode_shapes: set[tuple[int, int]] = set()
        #: bytes of the arrays every decode batch copied in and read back
        self.h2d_bytes = 0
        self.d2h_bytes = 0

    @property
    def device(self):
        """The JAX device holding this codec's tables."""
        return next(iter(self.dd.mat16.devices()))

    def place(self, device) -> None:
        """Move the tables to ``device``; later calls run there."""
        self.dd = jax.device_put(self.dd, device)

    @classmethod
    def from_artifact(cls, artifact) -> "OnPairDevice":
        """Open the device codec straight from a serialized DictArtifact —
        the shipping path: train on one host, save, decode on another."""
        from repro.core import registry
        if not registry.capabilities(artifact.codec).device_decodable:
            raise ValueError(
                f"codec {artifact.codec!r} is not device-decodable "
                "(registry capability); only bounded-entry token-stream "
                "dictionaries run on the kernels")
        return cls(PackedDictionary.build(artifact.entries))

    # ----------------------------------------------------------- encode
    def encode_batch(self, strings: list[bytes], use_pallas: bool = True,
                     max_tokens: int | None = None,
                     pad_len: int | None = None):
        """Compress a batch; returns (tokens int32[B,T], n_tokens int32[B]).

        With no ``pad_len``/``max_tokens`` the data width (and hence the jit
        trace) follows the longest string in the batch — fine for one-off
        calls, unbounded retraces under a mixed workload. Serving paths go
        through :meth:`encode_bucketed`, which pins both.
        """
        data, lens = pack_strings(strings, pad_len=pad_len)
        if max_tokens is None:
            max_tokens = data.shape[1] - 16 or 1
        self.encode_shapes.add((data.shape[0], data.shape[1]))
        fn = (onpair_encode.encode_batch_pallas if use_pallas
              else encode_batch_ref_jit)
        toks, n = fn(jnp.asarray(data), jnp.asarray(lens), self.dd, max_tokens)
        return np.asarray(toks), np.asarray(n)

    def _encode_cap(self, n: int) -> int:
        """Smallest bucket capacity >= n bytes, growing the set by doubling."""
        for cap in self.encode_len_caps:
            if n <= cap:
                return cap
        cap = self.encode_len_caps[-1]
        while cap < n:
            cap *= 2
            self.encode_len_caps.append(cap)
        return cap

    def encode_bucketed(self, strings: list[bytes],
                        use_pallas: bool = True) -> list[np.ndarray]:
        """Batch encode with a bounded set of compiled shapes.

        Strings are grouped into geometric byte-length buckets; each group is
        padded (with empty rows) to ``encode_pad_batch`` and encoded at the
        static shape (pad_batch, cap + 16) with ``max_tokens = cap`` (one
        token per byte is the worst case). Returns the per-string int32 token
        arrays in input order.
        """
        out: list[np.ndarray] = [None] * len(strings)  # type: ignore[list-item]
        pb = self.encode_pad_batch
        groups: dict[int, list[int]] = {}
        for i, s in enumerate(strings):
            groups.setdefault(self._encode_cap(max(len(s), 1)), []).append(i)
        for cap, idxs in sorted(groups.items()):
            for k in range(0, len(idxs), pb):
                sel = idxs[k : k + pb]
                chunk = [strings[i] for i in sel] + [b""] * (pb - len(sel))
                toks, n = self.encode_batch(chunk, use_pallas=use_pallas,
                                            max_tokens=cap, pad_len=cap)
                for j, i in enumerate(sel):
                    out[i] = toks[j, : n[j]]
        return out

    def warm_encode(self, use_pallas: bool = True) -> None:
        """AOT-compile every current encode bucket shape (store open time)."""
        for cap in list(self.encode_len_caps):
            self.encode_batch([b""] * self.encode_pad_batch,
                              use_pallas=use_pallas,
                              max_tokens=cap, pad_len=cap)

    def encode_to_bytes(self, strings: list[bytes], use_pallas: bool = True) -> list[bytes]:
        return [t.astype("<u2").tobytes()
                for t in self.encode_bucketed(strings, use_pallas=use_pallas)]

    # ----------------------------------------------------------- decode
    def decode_stream(self, tokens: np.ndarray, use_pallas: bool = True,
                      tile: int = 1024) -> bytes:
        """Decode one token stream (any concatenation of compressed strings)."""
        tokens = np.asarray(tokens, dtype=np.int32)
        n = tokens.size
        max_out = int(self.dictionary.lens[tokens].sum()) if n else 0
        if n == 0:
            return b""
        T = _pad_to(n, tile)
        padded = np.zeros(T, dtype=np.int32)
        padded[:n] = tokens
        if use_pallas:
            out, out_len = onpair_decode.decode_tokens_pallas(
                jnp.asarray(padded), jnp.int32(n), self.dd.mat16, self.dd.lens,
                max_out, tile=tile)
        else:
            from repro.kernels.ref import decode_ref
            out, out_len = jax.jit(decode_ref, static_argnames=("max_out",))(
                jnp.asarray(padded), jnp.int32(n), self.dd.mat16, self.dd.lens,
                max_out=max_out)
        out = np.asarray(out[: int(out_len)])
        return out.astype(np.uint8).tobytes()

    def decode_batch(self, tokens: np.ndarray, n_tokens: np.ndarray,
                     max_out: int, use_pallas: bool = True):
        """Batched random-access decode: tokens int32[B,T] -> list[bytes].

        Each step is a span: ``kernel.h2d`` (the copies in), ``kernel.dispatch``
        (the kernel call, which returns before the device is done),
        ``kernel.wait``, ``kernel.d2h`` (the copies back) and
        ``kernel.unpack`` (one ``bytes`` per row). ``h2d_bytes`` and
        ``d2h_bytes`` count the bytes of every array copied each way.
        """
        tokens = np.asarray(tokens, dtype=np.int32)
        n_tokens = np.asarray(n_tokens, dtype=np.int32)
        path = "pallas" if use_pallas else "ref"
        _DECODE_BATCHES[path].inc()
        fn = (onpair_decode.decode_compact if use_pallas
              else decode_batch_ref_jit)
        with TRACER.span("kernel.h2d"):
            tokens_d, n_tokens_d = jnp.asarray(tokens), jnp.asarray(n_tokens)
        with TRACER.span("kernel.dispatch"):
            out, olen = fn(tokens_d, n_tokens_d, self.dd.mat16, self.dd.lens,
                           max_out)
        with TRACER.span("kernel.wait"):
            out.block_until_ready()
            olen.block_until_ready()
        with TRACER.span("kernel.d2h"):
            out, olen = np.asarray(out), np.asarray(olen)
        self.h2d_bytes += tokens.nbytes + n_tokens.nbytes
        self.d2h_bytes += out.nbytes + olen.nbytes
        with TRACER.span("kernel.unpack"):
            return [out[i, : olen[i]].astype(np.uint8).tobytes()
                    for i in range(out.shape[0])]

    def multiget_decode(self, token_lists: list[np.ndarray],
                        pad_tokens: int | None = None,
                        pad_batch: int | None = None,
                        use_pallas: bool = True) -> list[bytes]:
        """Batched random-access decode of ragged token streams.

        Assembles the padded (B, T) matrix (see :func:`pack_token_matrix`)
        and runs the per-string decode kernel once; max_out = 16 * T is exact
        for OnPair16 (every entry <= 16 B). Returns only the real rows. The
        ``kernel.decode_batch`` span runs from packing (``kernel.pack``)
        through the last row's bytes.
        """
        if not token_lists:
            return []
        with TRACER.span("kernel.decode_batch", batch=len(token_lists)):
            with TRACER.span("kernel.pack"):
                tokens, n_tokens = pack_token_matrix(token_lists, pad_tokens,
                                                     pad_batch)
            out = self.decode_batch(tokens, n_tokens, 16 * tokens.shape[1],
                                    use_pallas=use_pallas)
        return out[: len(token_lists)]

    def roundtrip(self, strings: list[bytes], use_pallas: bool = True) -> list[bytes]:
        toks, n = self.encode_batch(strings, use_pallas=use_pallas)
        max_out = max((len(s) for s in strings), default=1)
        return self.decode_batch(toks, n, max_out, use_pallas=use_pallas)
