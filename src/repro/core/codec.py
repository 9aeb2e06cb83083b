"""Stateless Encoder / Decoder objects over a :class:`DictArtifact`.

The v2 split of ``StringCompressor``: training produces an immutable
artifact; per-string encode/decode are stateless operations *constructed
from* that artifact with an explicit backend selector:

    artifact = registry.train("onpair16", strings)
    artifact.save("dict.rpa")
    ...
    art = DictArtifact.load("dict.rpa")             # any host, no retraining
    corpus = Encoder(art).encode(strings)
    Decoder(art, backend="pallas").access(corpus, 17)

Backends:

* ``numpy``  — host path: greedy LPM parse / vectorised Algorithm-3 decode.
  Works for every registered codec; the only backend when JAX is absent.
* ``pallas`` — device path through :class:`repro.kernels.ops.OnPairDevice`
  (encode kernel + per-string decode kernel). Requires JAX and a codec whose
  registry capabilities say ``device_decodable`` (onpair, onpair16); the
  encoder also needs ``bounded_entries`` (onpair16: the device LPM parse
  assumes entries of at most 16 bytes).

Both backends produce byte-identical results; tests pin that equivalence.
"""

from __future__ import annotations

import numpy as np

import repro.core.registry as registry
from repro.core.api import CompressedCorpus
from repro.core.artifact import DictArtifact

BACKENDS = ("numpy", "pallas")


def _check_backend(artifact: DictArtifact, backend: str, device=None,
                   encode: bool = False):
    """Resolve + validate; returns an OnPairDevice for the pallas backend
    (``device`` when one is supplied)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r} (one of {BACKENDS})")
    if backend == "numpy":
        return None
    caps = registry.capabilities(artifact.codec)
    if not caps.device_decodable:
        raise ValueError(f"codec {artifact.codec!r} is not device-decodable "
                         "(registry capability); use backend='numpy'")
    if encode and not caps.bounded_entries:
        raise ValueError(f"codec {artifact.codec!r} has entries over 16 "
                         "bytes, which the device encoder does not parse "
                         "(registry capability bounded_entries); use "
                         "backend='numpy'")
    if device is not None:
        return device
    try:
        from repro.kernels.ops import OnPairDevice
    except Exception as e:  # jax missing on this host
        raise ValueError(f"backend='pallas' unavailable: {e}") from None
    return OnPairDevice.from_artifact(artifact)


class Encoder:
    """Stateless per-string encoder constructed from an artifact.

    ``codec`` optionally supplies an already-built host codec for the same
    artifact (e.g. a store's compressor) so its dictionary tables are
    shared instead of rebuilt.
    """

    def __init__(self, artifact: DictArtifact, backend: str = "numpy",
                 codec=None, device=None):
        self.artifact = artifact
        self.backend = backend
        # ``device`` optionally supplies an already-built OnPairDevice for the
        # same artifact (e.g. a store's decode device) so its packed tables
        # and compiled kernels are shared instead of rebuilt.
        self._device = _check_backend(artifact, backend, device, encode=True)
        # the host codec (and its PackedDictionary rebuild) is only needed on
        # the numpy path; the pallas path decodes through the device tables
        self._codec = None
        if self._device is None:
            self._codec = (codec if codec is not None
                           else registry.codec_from_artifact(artifact))

    def warm(self) -> None:
        """AOT-compile the device encode buckets (no-op on the numpy path)."""
        if self._device is not None:
            self._device.warm_encode()

    def encode(self, strings: list[bytes]) -> CompressedCorpus:
        """Compress every string independently into one corpus."""
        if self._device is None:
            return self._codec.compress(strings)
        toks = self._device.encode_bucketed(strings)
        counts = np.fromiter((t.size for t in toks), dtype=np.int64,
                             count=len(toks))
        offsets = np.zeros(len(toks) + 1, dtype=np.int64)
        np.cumsum(counts * 2, out=offsets[1:])
        payload = (np.concatenate(toks).astype("<u2").view(np.uint8)
                   if len(toks) else np.zeros(0, dtype=np.uint8))
        return CompressedCorpus(payload=payload, offsets=offsets,
                                raw_bytes=sum(len(s) for s in strings),
                                meta={"compressor":
                                      registry.resolve(self.artifact.codec)})

    def encode_one(self, s: bytes) -> bytes:
        """Compressed payload of a single string."""
        if self._device is None:
            corpus = self._codec.compress([s])
            return corpus.string_payload(0)
        return self._device.encode_to_bytes([s])[0]


class Decoder:
    """Stateless decoder constructed from an artifact."""

    def __init__(self, artifact: DictArtifact, backend: str = "numpy"):
        self.artifact = artifact
        self.backend = backend
        self._device = _check_backend(artifact, backend)
        self._codec = (registry.codec_from_artifact(artifact)
                       if self._device is None else None)
        self._caps = registry.capabilities(artifact.codec)

    @property
    def dictionary(self):
        """The frozen PackedDictionary (token-stream codecs only)."""
        if self._device is not None:
            return self._device.dictionary
        return getattr(self._codec, "dictionary", None)

    def decode_all(self, corpus: CompressedCorpus) -> bytes:
        """Sequential full-corpus decode (concatenated strings)."""
        if self._device is not None:
            tokens = np.asarray(corpus.payload.view("<u2"), dtype=np.int32)
            return self._device.decode_stream(tokens)
        return self._codec.decompress_all(corpus)

    def access(self, corpus: CompressedCorpus, i: int) -> bytes:
        """Random access: string ``i`` alone."""
        if self._device is not None:
            return self.multiget(corpus, [i])[0]
        return self._codec.access(corpus, i)

    def multiget(self, corpus: CompressedCorpus, ids) -> list[bytes]:
        """Batched random access; one kernel launch on the pallas backend."""
        if self._device is not None:
            lists = [np.asarray(corpus.string_tokens(int(i)), dtype=np.int32)
                     for i in ids]
            return self._device.multiget_decode(lists)
        return [self._codec.access(corpus, int(i)) for i in ids]
