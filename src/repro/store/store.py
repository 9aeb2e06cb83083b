"""CompressedStringStore — batched random-access serving over OnPair corpora.

The paper's headline property (per-string independent compression => O(1)
random access) turned into a serving subsystem: a trained OnPair/OnPair16
dictionary plus a :class:`~repro.core.api.CompressedCorpus` become an
in-memory store answering ``get(i)`` / ``multiget(ids)`` / ``scan(lo, hi)``.

Hot path (``multiget``): cache misses are routed through the segment layer
to their token streams, *length-bucketed* into a small set of static padded
``(B, T)`` shapes, and decoded by the Pallas per-string kernel
(``repro.kernels.onpair_decode.decode_compact`` via
``OnPairDevice.multiget_decode``). T counts 16-byte dictionary rows: a
token of OnPair16 is one row, a token of an unbounded OnPair entry over 16
bytes a chain of several. Pinning both the batch dim and the row dim to at
most ``num_buckets`` bucket capacities keeps the number of jit-compiled
decode shapes bounded (<= num_buckets, default 4) no matter the query mix.
The store serves from the vectorised numpy
``PackedDictionary.decode_tokens`` path when asked to (``backend="numpy"``),
and under ``backend="auto"`` where jax is not installed or ``REPRO_NO_JAX``
is set. ``stats_snapshot()`` names the backend it resolved
and, for ``jax``, the device.
"""

from __future__ import annotations

import heapq
import json
import os
import threading
import time
from itertools import islice

import numpy as np

from repro.core import registry
from repro.core.api import CompressedCorpus
from repro.core.artifact import DictArtifact
from repro.core.codec import Encoder
from repro.core.index import SegmentIndex, dump_indexes, load_indexes
from repro.core.packed import PackedDictionary
from repro.obs import TRACER
from repro.store.cache import LRUCache
from repro.store.segment import SegmentedCorpus
from repro.store.stats import StoreStats


def device_codec():
    """:class:`~repro.kernels.ops.OnPairDevice`, or None where jax is not
    installed or ``REPRO_NO_JAX`` opts out (numpy-only serving hosts). Any
    other failure to import the kernels — a broken jax or libtpu install —
    propagates: it must not turn into a quiet numpy store."""
    if os.environ.get("REPRO_NO_JAX"):
        return None
    try:
        from repro.kernels.ops import OnPairDevice
    except ModuleNotFoundError as e:
        if (e.name or "").split(".")[0] not in ("jax", "jaxlib"):
            raise
        return None
    return OnPairDevice


#: quantiles of the corpus token-count distribution that seed the bucket
#: capacities (the last one is stretched to cover the true maximum).
_BUCKET_QUANTILES = (0.5, 0.9, 0.99, 1.0)


def _ceil8(x: int) -> int:
    return max(8, (int(x) + 7) // 8 * 8)


def write_json_atomic(path: str, obj: dict) -> None:
    """Write JSON via temp-file + rename so readers never see a torn file."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


class CompressedStringStore:
    """Queryable in-memory store over one compressed corpus.

    ``source`` is either a trained token-stream codec (the pre-v2 calling
    convention), a serialized :class:`DictArtifact` — the store is exactly
    the consumer the artifact split exists for: open a dictionary that was
    trained elsewhere and serve, no trainer state required — or an
    ``(artifact, codec)`` pair when both are already loaded (shared-
    dictionary layouts open N stores without N table or artifact rebuilds).
    """

    def __init__(self, source, corpus: CompressedCorpus,
                 *, strings_per_segment: int = 4096,
                 cache_bytes: int = 8 << 20, batch_size: int = 256,
                 num_buckets: int = 4, backend: str = "auto",
                 use_pallas: bool = True):
        self._artifact: DictArtifact | None
        if isinstance(source, tuple):
            self._artifact, compressor = source
        elif isinstance(source, DictArtifact):
            self._artifact = source
            compressor = registry.codec_from_artifact(source)
        else:
            self._artifact = None
            compressor = source
        if getattr(compressor, "dictionary", None) is None:
            raise ValueError("source must be a trained token-stream codec "
                             "or a DictArtifact (train() first)")
        caps = registry.capabilities(compressor.name)
        if not caps.token_stream:
            raise ValueError("store requires a token-stream codec "
                             f"(registry capability), got {compressor.name!r}")
        if num_buckets < 1 or num_buckets > len(_BUCKET_QUANTILES):
            raise ValueError(f"num_buckets must be in 1..{len(_BUCKET_QUANTILES)}")
        self.compressor = compressor
        self.dictionary: PackedDictionary = compressor.dictionary
        self.corpus = corpus
        self.segments = SegmentedCorpus.from_corpus(corpus, strings_per_segment)
        self.cache = LRUCache(cache_bytes)
        self.batch_size = int(batch_size)
        self.num_buckets = int(num_buckets)
        self.use_pallas = use_pallas
        self._lock = threading.Lock()
        # reverse-lookup state: per-segment indexes (built lazily on the
        # first locate/scan_prefix, eagerly at seal time once active) and
        # the query-side encoder (lazy: most stores never locate)
        self._seg_indexes: dict[int, SegmentIndex] = {}
        self._locate_encoder: Encoder | None = None
        # hot/cold tiering (repro.store.tier); None until enable_tiering()
        self.tier = None
        # the JAX device the tables live on (None: JAX's default); a
        # sharded open spreads shards over the chips (_place)
        self._placement = None
        # the device tables warm_decode last compiled every bucket for
        self._warmed = None

        # ----- backend resolution: per-codec registry capability, not an
        # isinstance/variant16 probe — an artifact opened on a jax-less host
        # resolves to numpy, a device-decodable codec routes to the kernels.
        if backend not in ("auto", "jax", "numpy"):
            raise ValueError(f"unknown backend {backend!r}")
        codec = (device_codec() if backend != "numpy"
                 and caps.device_decodable else None)
        if backend == "auto":
            backend = "jax" if codec is not None else "numpy"
        elif backend == "jax" and codec is None:
            raise ValueError(
                "jax backend unavailable: " +
                ("jax not importable (or REPRO_NO_JAX set)"
                 if caps.device_decodable else
                 f"codec {compressor.name!r} is not device-decodable "
                 "(registry capability)"))
        self.backend = backend
        # stats carries the resolved backend as a metric label, so it is
        # created only once backend resolution has run
        self.stats = StoreStats(backend=backend)
        self._device = self._new_device(self.dictionary)
        self._set_bucket_caps(self._string_rows(corpus))

    def _new_device(self, dictionary: PackedDictionary):
        """Device tables for ``dictionary`` on this store's placement (None
        on the numpy backend)."""
        if self.backend != "jax":
            return None
        return device_codec()(dictionary, self._placement)

    def _place(self, device) -> None:
        """Keep this store's device tables on ``device`` from now on."""
        self._placement = device
        if self._device is not None:
            self._device.place(device)

    def _string_rows(self, corpus: CompressedCorpus) -> np.ndarray:
        """16-byte dictionary rows of each string of ``corpus``, i64: its
        token count where every entry is one row (OnPair16)."""
        return self.dictionary.stream_rows(
            corpus.payload.view("<u2"),
            np.asarray(corpus.offsets, dtype=np.int64) // 2)

    def _set_bucket_caps(self, counts: np.ndarray) -> None:
        """Length buckets: static row capacities from corpus quantiles."""
        if counts.size == 0:
            caps = [8]
        else:
            qs = _BUCKET_QUANTILES[-self.num_buckets:]
            caps = sorted({_ceil8(np.quantile(counts, q)) for q in qs})
            max_count = int(counts.max())
            if caps[-1] < max_count:
                caps.append(_ceil8(max_count))
                if len(caps) > self.num_buckets:
                    caps = caps[-self.num_buckets:]
        self.bucket_caps = np.asarray(caps, dtype=np.int64)

    # ------------------------------------------------------------ construction
    @classmethod
    def build(cls, strings: list[bytes], *, codec: str | None = None,
              variant16: bool = True, sample_bytes: int = 4 << 20,
              seed: int = 0, **store_kw) -> "CompressedStringStore":
        """Train a dictionary on ``strings``, compress them, open a store.

        ``codec`` is any registered token-stream codec name; the legacy
        ``variant16`` flag maps to onpair16/onpair when ``codec`` is None.
        """
        if codec is None:
            codec = "onpair16" if variant16 else "onpair"
        comp = registry.create(codec, sample_bytes=sample_bytes, seed=seed)
        comp.train(strings)
        return cls(comp, comp.compress(strings), **store_kw)

    # ------------------------------------------------------------- persistence
    #: directory layout written by save() / read by open()
    _DICT_FILE = "dictionary.rpa"
    _CORPUS_FILE = "corpus.rpc"
    _META_FILE = "store.json"
    #: optional reverse-lookup sidecar (per-segment fingerprint tables +
    #: sort permutations); loaders validate it against the live
    #: segmentation and silently rebuild on any mismatch
    _INDEX_FILE = "index.npz"
    #: manifest of the versioned (writable-store) directory layout
    _CURRENT_FILE = "current.json"
    #: construction params persisted in store.json and restored by open()
    _STORE_KW = ("strings_per_segment", "cache_bytes", "batch_size",
                 "num_buckets")

    @property
    def artifact(self) -> DictArtifact:
        """The store's dictionary as an immutable, serializable artifact."""
        if self._artifact is None:
            self._artifact = self.compressor.to_artifact()
        return self._artifact

    def snapshot_corpus(self) -> CompressedCorpus:
        """The store's full compressed payload as one corpus. The writable
        subclass overrides this to flatten sealed segments + tail — the
        construction-time ``self.corpus`` does not cover appended data."""
        return self.corpus

    def store_meta(self, **extra) -> dict:
        """The store.json payload: codec + construction params (+ extras)."""
        meta = {"format_version": 1, "codec": self.artifact.codec,
                "n_strings": self.n_strings,
                "strings_per_segment": self.segments.strings_per_segment,
                "cache_bytes": self.cache.capacity_bytes,
                "batch_size": self.batch_size,
                "num_buckets": self.num_buckets}
        meta.update(extra)
        return meta

    def save(self, dir_path: str) -> None:
        """Persist dictionary artifact + compressed corpus + store config so
        :meth:`open` serves identical results without retraining."""
        os.makedirs(dir_path, exist_ok=True)
        self.artifact.save(os.path.join(dir_path, self._DICT_FILE))
        self.corpus.save(os.path.join(dir_path, self._CORPUS_FILE))
        with self._lock:
            blob = self._dump_index_locked()
            tier_meta = self._tier_meta_locked()
        write_json_atomic(os.path.join(dir_path, self._META_FILE),
                          self.store_meta(**tier_meta))
        if blob is not None:
            with open(os.path.join(dir_path, self._INDEX_FILE), "wb") as f:
                f.write(blob)
        if tier_meta:
            self.tier.copy_cold_files(tier_meta["cold_segments"], dir_path)

    @classmethod
    def open_corpus_dir(cls, dir_path: str, source,
                        mmap: bool = True, **overrides) -> "CompressedStringStore":
        """Open a directory holding corpus.rpc + store.json against an
        already-loaded artifact or codec (shared-dictionary layouts:
        sharding opens N corpora against one dictionary)."""
        with open(os.path.join(dir_path, cls._META_FILE)) as f:
            meta = json.load(f)
        corpus = CompressedCorpus.load(
            os.path.join(dir_path, cls._CORPUS_FILE), mmap=mmap)
        kw = {k: meta[k] for k in cls._STORE_KW}
        kw.update(overrides)
        store = cls(source, corpus, **kw)
        store._load_index(dir_path)
        store._attach_tier(dir_path, meta)
        return store

    @classmethod
    def _resolve_current(cls, dir_path: str) -> str:
        """Follow a versioned directory's ``current.json`` manifest to its
        current generation subdirectory; a plain flat store directory
        resolves to itself."""
        cur = os.path.join(dir_path, cls._CURRENT_FILE)
        if os.path.exists(cur):
            with open(cur) as f:
                return os.path.join(dir_path, json.load(f)["current"])
        return dir_path

    @classmethod
    def open(cls, dir_path: str, mmap: bool = True,
             **overrides) -> "CompressedStringStore":
        """Open a saved store: mmap the artifact + corpus, no retraining.
        ``overrides`` replace saved construction params (e.g. ``backend=``).
        A versioned (writable-store) directory opens read-only at its
        current generation."""
        dir_path = cls._resolve_current(dir_path)
        artifact = DictArtifact.load(
            os.path.join(dir_path, cls._DICT_FILE), mmap=mmap)
        return cls.open_corpus_dir(dir_path, artifact, mmap=mmap, **overrides)

    # ----------------------------------------------------------------- tiering
    def enable_tiering(self, **params):
        """Get-or-create the store's :class:`~repro.store.tier.TierManager`.
        Parameters only apply on first creation; a later call with different
        thresholds updates them in place."""
        from repro.store.tier import TierManager
        if self.tier is None:
            self.tier = TierManager(self, **params)
        elif params:
            for k in ("demote_below", "promote_above", "halflife_s"):
                if k in params:
                    setattr(self.tier, k, float(params[k]))
        return self.tier

    def _tier_meta_locked(self) -> dict:
        """store.json extras describing the tier state (``{}`` when the
        tier is off or empty — old stores stay byte-identical)."""
        if self.tier is None or not self.tier.cold:
            return {}
        return {"tier_params": self.tier.params(),
                "cold_segments": self.tier.cold_items_locked()}

    def _attach_tier(self, dir_path: str, meta: dict) -> None:
        """Re-adopt cold segments persisted by a save (called after
        ``_load_index`` so both sidecars validate against the same live
        segmentation)."""
        cold = meta.get("cold_segments")
        if not cold:
            return
        tier = self.enable_tiering(**meta.get("tier_params", {}))
        tier.attach(dir_path, cold)

    # -------------------------------------------------------------- tail hooks
    # A store may hold strings beyond the sealed SegmentedCorpus: the writable
    # subclass (repro.store.mutable) keeps an open *tail* of appended strings.
    # The read path is written against these hooks so get/multiget/scan/stats
    # stay correct across sealed + tail data; the read-only base has no tail.
    def _tail_n(self) -> int:
        return 0

    def _tail_payload_bytes(self) -> int:
        return 0

    def _tail_string_tokens(self, local: int) -> np.ndarray:
        raise IndexError(f"tail string {local} does not exist "
                         "(read-only store has no tail)")

    def _tail_scan(self, lo: int, hi: int) -> list[bytes]:
        return []

    def _tail_locate(self, payload: bytes) -> int | None:
        """Tail-local id of the string whose encoded form is ``payload``.
        Call under ``self._lock``; the read-only base has no tail."""
        return None

    def _tail_prefix_hits(self, prefix: bytes,
                          after: tuple[bytes, int] | None
                          ) -> list[tuple[bytes, int]]:
        """Sorted ``(string, gid)`` tail matches of ``prefix`` past the
        ``after`` cursor. Call under ``self._lock``."""
        return []

    def _string_tokens(self, gid: int) -> np.ndarray:
        """u16 token IDs of global string ``gid`` (sealed or tail).
        Call under ``self._lock``."""
        sealed = self.segments.n_strings
        if gid < sealed:
            return self.segments.string_tokens(gid)
        return self._tail_string_tokens(gid - sealed)

    # ---------------------------------------------------------------- queries
    @property
    def n_sealed(self) -> int:
        """Strings living in sealed (immutable) segments."""
        return self.segments.n_strings

    @property
    def n_strings(self) -> int:
        return self.segments.n_strings + self._tail_n()

    def __len__(self) -> int:
        return self.n_strings

    @property
    def memory_bytes(self) -> int:
        """Resident footprint: compressed payload + offsets of every sealed
        segment (including segments sealed from an appended tail, which the
        construction-time corpus does not cover) + the full dictionary
        (decode matrix and LPM tables included) + decoded-string cache + any
        unsealed tail payload. Demoted (cold) segments do not count: their
        payload/offsets are ``np.memmap`` views over the ``cold-*.rlz``
        container, so the kernel can drop those pages under pressure."""
        cold = self.tier.cold if self.tier is not None else ()
        seg_bytes = sum(s.payload_bytes + s.offsets.nbytes
                        for s in self.segments.segments
                        if s.index not in cold)
        return (seg_bytes + self.dictionary.resident_bytes
                + self.cache.current_bytes + self._tail_payload_bytes())

    def get(self, i: int) -> bytes:
        """Point lookup of string ``i``."""
        return self.multiget([i])[0]

    def multiget(self, ids) -> list[bytes]:
        """Batched point lookup; duplicates decode once, order is preserved.

        Raises IndexError if any id is out of ``[0, n_strings)`` (before any
        decode work happens).
        """
        t0 = time.perf_counter()
        with TRACER.span("store.multiget"):
            ids = [int(i) for i in ids]
            n = self.n_strings
            for i in ids:
                if not 0 <= i < n:
                    raise IndexError(f"string id {i} out of range [0, {n})")
            with self._lock:
                if self.tier is not None:
                    self.tier.note_reads_locked(ids)
                results: dict[int, bytes] = {}
                misses: list[int] = []
                for i in ids:  # unique-preserving probe: duplicates decode once
                    if i in results:
                        continue
                    hit = self.cache.get(i)
                    if hit is not None:
                        results[i] = hit
                    else:
                        results[i] = b""  # claimed; overwritten by decode below
                        misses.append(i)
                if misses:
                    with TRACER.span("store.decode", batch=len(misses)):
                        self._decode_misses(misses, results)
                out = [results[i] for i in ids]
        self.stats.record_multiget(len(ids), time.perf_counter() - t0)
        return out

    def scan(self, lo: int, hi: int) -> list[bytes]:
        """Decode the contiguous id range [lo, hi) segment by segment: each
        segment's covered slice is one token stream, decoded in a single
        vectorised pass and split on per-string byte boundaries. Ranges may
        extend past the sealed segments into the unsealed tail."""
        n = self.n_strings
        if not (0 <= lo <= hi <= n):
            raise IndexError(f"scan range [{lo}, {hi}) not within [0, {n}]")
        with self._lock:
            out = self._scan_locked(lo, hi)
            self.stats.scan_strings += hi - lo
        return out

    def _scan_locked(self, lo: int, hi: int) -> list[bytes]:
        out: list[bytes] = []
        for seg in self.segments.overlapping(lo, hi):
            s_lo = max(lo, seg.base_id)
            s_hi = min(hi, seg.base_id + seg.n_strings)
            if s_lo >= s_hi:
                continue
            l0, l1 = s_lo - seg.base_id, s_hi - seg.base_id
            if self.tier is not None and seg.index in self.tier.cold:
                out.extend(self.tier.decode_range_locked(seg.index, l0, l1))
                continue
            tokens = np.asarray(seg.tokens(l0, l1), dtype=np.int64)
            decoded = self.dictionary.decode_tokens(tokens)
            counts = seg.token_counts()[l0:l1]
            out.extend(self._split_decoded(decoded, tokens, counts))
        sealed = self.segments.n_strings
        if hi > sealed:
            out.extend(self._tail_scan(max(lo, sealed) - sealed, hi - sealed))
        return out

    # --------------------------------------------------- reverse lookup
    #: optimistic encode attempts before locate takes the store lock for
    #: the whole encode+probe (mirrors MutableStringStore.extend: a
    #: compact() swapping the dictionary between the query parse and the
    #: probe would compare encodings from different generations — byte
    #: verification would then give false misses, or even a false hit if
    #: two generations encode different strings to the same bytes)
    _MAX_LOCATE_RETRIES = 3

    def locate(self, s: bytes) -> int | None:
        """Exact-match reverse lookup: the id whose ``get`` returns ``s``.

        The query is encoded once against the store's dictionary and
        compared in *compressed* form — no decompression on the probe
        path. Duplicated strings resolve to their lowest id; absent
        strings return ``None``. Exact match only: see :meth:`scan_prefix`
        for prefix enumeration.
        """
        return self.locate_batch([s])[0]

    def locate_batch(self, strings) -> list[int | None]:
        """Batched :meth:`locate`; one encoder pass, order preserved."""
        strings = [bytes(s) for s in strings]
        if not strings:
            return []
        t0 = time.perf_counter()
        out = None
        for _ in range(self._MAX_LOCATE_RETRIES):
            version = getattr(self, "version_id", 0)
            payloads = self._encode_queries(strings)
            with self._lock:
                if getattr(self, "version_id", 0) == version:
                    out = [self._locate_payload_locked(p) for p in payloads]
                    break
            # compact() swapped generations mid-parse: re-encode and retry
        if out is None:
            # retries exhausted: encode under the store lock itself, where
            # no swap can interleave (same escape hatch as extend())
            with self._lock:
                corpus = self._query_encoder().encode(strings)
                out = [self._locate_payload_locked(corpus.string_payload(i))
                       for i in range(len(strings))]
        n_hits = sum(1 for r in out if r is not None)
        self.stats.record_locate(len(strings), n_hits,
                                 time.perf_counter() - t0)
        return out

    def scan_prefix(self, prefix: bytes, limit: int | None = 100,
                    after: tuple[bytes, int] | None = None
                    ) -> list[tuple[int, bytes]]:
        """Strings starting with ``prefix``: ``[(id, string), ...]`` in
        ``(string, id)`` order.

        Served from the per-segment sorted sidecars (binary search + one
        independent decode per probed entry) merged with a linear filter
        over the unsealed tail. ``after`` is an exclusive ``(string, id)``
        resume cursor for pagination; ``limit=None`` returns every match.
        Results reflect the dictionary generation at call time — a
        concurrent ``compact()`` does not change ids, but paginating
        across one may re-observe strings the swap re-filed.
        """
        prefix = bytes(prefix)
        with self._lock:
            runs: list[list[tuple[bytes, int]]] = []
            for seg in self.segments.segments:
                if seg.n_strings == 0:
                    continue
                idx = self._segment_index_locked(seg)
                base = seg.base_id
                seg_after = ((after[0], after[1] - base)
                             if after is not None else None)
                hits = idx.scan_prefix(
                    prefix, limit,
                    lambda loc, b=base: self._decode_one_locked(b + loc),
                    after=seg_after)
                if hits:
                    runs.append([(s, base + loc) for loc, s in hits])
            tail_hits = self._tail_prefix_hits(prefix, after)
            if tail_hits:
                runs.append(tail_hits)
            merged = heapq.merge(*runs)
            if limit is not None:
                merged = islice(merged, limit)
            out = [(gid, s) for s, gid in merged]
        self.stats.prefix_scans += 1
        self.stats.scan_strings += len(out)
        return out

    def _query_encoder(self) -> Encoder:
        """Encoder for query strings; shares the compressor's tables. The
        writable subclass returns its tail encoder instead (identical
        encodings by construction — same generation, same tables)."""
        if self._locate_encoder is None:
            self._locate_encoder = Encoder(self.artifact,
                                           codec=self.compressor)
        return self._locate_encoder

    def _encode_queries(self, strings: list[bytes]) -> list[bytes]:
        """Compressed form of each query, current dictionary generation."""
        corpus = self._query_encoder().encode(strings)
        buf = corpus.payload.tobytes()
        off = corpus.offsets
        return [buf[off[i]:off[i + 1]] for i in range(len(strings))]

    def _locate_payload_locked(self, payload: bytes) -> int | None:
        """Probe sealed segments in id order, then the tail; first
        byte-verified hit is the lowest global id."""
        for seg in self.segments.segments:
            if seg.n_strings == 0:
                continue
            idx = self._segment_index_locked(seg)
            loc = idx.locate(payload, seg.payload, seg.offsets)
            if loc is not None:
                return seg.base_id + loc
        loc = self._tail_locate(payload)
        if loc is not None:
            return self.segments.n_strings + loc
        return None

    def _segment_index_locked(self, seg) -> SegmentIndex:
        """The segment's reverse-lookup index, built on first use. The
        count re-check guards against segment-slot reuse (appending to an
        empty corpus replaces the placeholder segment in slot 0)."""
        idx = self._seg_indexes.get(seg.index)
        if idx is not None and idx.n == seg.n_strings:
            return idx
        raw = self._scan_locked(seg.base_id, seg.base_id + seg.n_strings)
        idx = SegmentIndex.build(seg.payload, seg.offsets, raw)
        self._seg_indexes[seg.index] = idx
        return idx

    def _decode_one_locked(self, gid: int) -> bytes:
        """One string through the LRU cache (scan_prefix probe path)."""
        hit = self.cache.get(gid)
        if hit is not None:
            return hit
        results = {gid: b""}
        self._decode_misses([gid], results)
        return results[gid]

    def _dump_index_locked(self) -> bytes | None:
        """Serialised sidecar of every up-to-date segment index, or None
        when nothing is built (lazy rebuild is cheaper than a forced
        decode of segments nobody has located in)."""
        live: dict[int, tuple[int, SegmentIndex]] = {}
        for seg in self.segments.segments:
            idx = self._seg_indexes.get(seg.index)
            if idx is not None and seg.n_strings and idx.n == seg.n_strings:
                live[seg.index] = (seg.base_id, idx)
        return dump_indexes(live) if live else None

    def _load_index(self, dir_path: str) -> None:
        """Adopt a persisted index sidecar if it matches the live
        segmentation (position + base id + count); mismatches are dropped
        per segment and rebuilt lazily."""
        path = os.path.join(dir_path, self._INDEX_FILE)
        if not os.path.exists(path):
            return
        with open(path, "rb") as f:
            data = f.read()
        with self._lock:
            layout = {seg.index: (seg.base_id, seg.n_strings)
                      for seg in self.segments.segments if seg.n_strings}
            self._seg_indexes.update(load_indexes(data, layout))

    def stats_snapshot(self) -> dict:
        snap = self.stats.snapshot(self.cache.stats())
        if self._device is not None:
            dev = self._device.device
            snap["device"] = {"platform": dev.platform,
                              "kind": dev.device_kind, "id": dev.id}
        snap.update(backend=self.backend, n_strings=self.n_strings,
                    n_sealed_strings=self.n_sealed,
                    n_tail_strings=self._tail_n(),
                    n_segments=self.segments.n_segments,
                    bucket_caps=[int(c) for c in self.bucket_caps],
                    memory_bytes=self.memory_bytes)
        if self.tier is not None:
            snap["tier"] = self.tier.snapshot()
        return snap

    # --------------------------------------------------------------- internals
    def _split_decoded(self, decoded: bytes, tokens: np.ndarray,
                       counts: np.ndarray) -> list[bytes]:
        """Split one decoded byte run back into per-string slices."""
        tok_lens = self.dictionary.lens[tokens].astype(np.int64)
        byte_cum = np.zeros(tokens.size + 1, dtype=np.int64)
        np.cumsum(tok_lens, out=byte_cum[1:])
        bounds = byte_cum[np.concatenate(([0], np.cumsum(counts)))]
        return [decoded[int(bounds[k]) : int(bounds[k + 1])]
                for k in range(len(counts))]

    def _decode_misses(self, misses: list[int], results: dict[int, bytes]) -> None:
        if self.tier is not None and self.tier.cold:
            hot, cold = self.tier.split_misses_locked(misses)
            if cold:
                self.tier.decode_misses_locked(cold, results)
                self.stats.host_strings += sum(map(len, cold.values()))
                with TRACER.span("store.cache_put"):
                    for pairs in cold.values():
                        for gid, _ in pairs:
                            self.cache.put(gid, results[gid])
                misses = hot
                if not misses:
                    return
        with TRACER.span("store.tokens", batch=len(misses)):
            token_lists = [np.asarray(self._string_tokens(i), dtype=np.int32)
                           for i in misses]
        if self._device is not None:
            self._decode_jax(misses, token_lists, results)
        else:
            self._decode_numpy(misses, token_lists, results)
        with TRACER.span("store.cache_put", batch=len(misses)):
            for i in misses:
                self.cache.put(i, results[i])

    def _decode_jax(self, misses: list[int], token_lists: list[np.ndarray],
                    results: dict[int, bytes]) -> None:
        device = self._device
        if device.chained and self._warmed is not device:
            self.warm_decode()
        # rows per string, counted once here for the buckets and the kernel
        # (a bounded dictionary reads only the sizes: a token is a row)
        sizes = [t.size for t in token_lists]
        counts = self.dictionary.stream_rows(
            np.concatenate(token_lists) if device.chained else (),
            np.cumsum([0] + sizes))
        if counts.size and int(counts.max()) > int(self.bucket_caps[-1]):
            # appended strings can exceed every build-time bucket: grow a new
            # top bucket instead of indexing past the table. Growth is
            # geometric (at least 2x the previous top) so steadily longer
            # appends mint O(log max_tokens) extra jit shapes, not one per
            # oversized batch.
            self.bucket_caps = np.append(
                self.bucket_caps,
                max(_ceil8(int(counts.max())), 2 * int(self.bucket_caps[-1])))
        buckets = np.searchsorted(self.bucket_caps, counts, side="left")
        for b in np.unique(buckets):
            cap = int(self.bucket_caps[int(b)])
            members = [k for k in range(len(misses)) if buckets[k] == b]
            for c0 in range(0, len(members), self.batch_size):
                chunk = members[c0 : c0 + self.batch_size]
                lists = [token_lists[k] for k in chunk]
                h2d, d2h = device.h2d_bytes, device.d2h_bytes
                t0 = time.perf_counter()
                decoded = device.multiget_decode(
                    lists, pad_tokens=cap, pad_batch=self.batch_size,
                    use_pallas=self.use_pallas, rows=counts[chunk])
                dt = time.perf_counter() - t0
                for k, val in zip(chunk, decoded):
                    results[misses[k]] = val
                self.stats.record_decode_batch(
                    (self.batch_size, cap), len(chunk),
                    sum(len(v) for v in decoded),
                    sum(t.size for t in lists), dt, device=True,
                    h2d_bytes=device.h2d_bytes - h2d,
                    d2h_bytes=device.d2h_bytes - d2h,
                    n_rows=int(counts[chunk].sum()))

    def warm_decode(self) -> None:
        """Compile the device decode at every bucket shape, by one empty
        batch each; ``first_batch_s`` keeps each compile's seconds. A store
        over a chained dictionary calls it before its first decode: its
        buckets count rows, which a caller picking warm-up strings by their
        token counts cannot aim at."""
        self._warmed = self._device
        for cap in self.bucket_caps:
            shape = (self.batch_size, int(cap))
            t0 = time.perf_counter()
            self._device.multiget_decode(
                [np.zeros(0, dtype=np.int32)], pad_tokens=shape[1],
                pad_batch=shape[0], use_pallas=self.use_pallas)
            self.stats.first_batch_s.setdefault(shape,
                                                time.perf_counter() - t0)

    def _decode_numpy(self, misses: list[int], token_lists: list[np.ndarray],
                      results: dict[int, bytes]) -> None:
        """Fallback: all misses concatenate into ONE token stream (strings are
        independent), decoded by the vectorised host path and re-split."""
        t0 = time.perf_counter()
        counts = np.asarray([t.size for t in token_lists], dtype=np.int64)
        tokens = (np.concatenate(token_lists).astype(np.int64)
                  if token_lists else np.zeros(0, dtype=np.int64))
        decoded = self.dictionary.decode_tokens(tokens)
        parts = self._split_decoded(decoded, tokens, counts)
        dt = time.perf_counter() - t0
        for i, val in zip(misses, parts):
            results[i] = val
        self.stats.record_decode_batch(
            (len(misses), int(counts.max()) if counts.size else 0),
            len(misses), len(decoded), tokens.size, dt, device=False)
