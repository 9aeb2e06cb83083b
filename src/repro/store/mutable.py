"""MutableStringStore — the write path of the serving subsystem.

OnPair compresses every string independently against a trained dictionary,
so *new* strings can be parsed against a **frozen** dictionary without any
retraining — the ingestion model of an in-memory database. The mutable
store layers that lifecycle over :class:`CompressedStringStore`:

* ``append``/``extend`` parse incoming strings with the saved-artifact
  :class:`~repro.core.codec.Encoder` into an open **tail** (a list of
  per-string token-stream payloads);
* once the tail reaches ``strings_per_segment`` strings it is **sealed**
  into the immutable :class:`~repro.store.segment.SegmentedCorpus` layout —
  reads (`get`/`multiget`/`scan`) answer consistently across sealed + tail
  data the whole time;
* a :class:`~repro.store.drift.DriftMonitor` watches the achieved ratio of
  appended data against the train-time ratio; when the distribution drifts,
  ``compact()`` re-trains a dictionary on the live data and rewrites every
  segment against it, swapping the store's state (and, when the store is
  backed by a directory, a new **versioned artifact directory** via the
  atomic-manifest pattern of ``write_json_atomic``).

On disk a mutable store is a *versioned* directory::

    <dir>/current.json     atomic manifest: {"current": "v0000", ...}
    <dir>/v0000/           one flat store layout per dictionary generation
        dictionary.rpa       (train-once artifact)
        corpus.rpc           (sealed segments + unsealed tail strings)
        store.json           (construction params + n_tail + drift state)
    <dir>/v0001/           written by compact(); manifest swap is atomic

``open()`` also accepts a plain read-only store directory (no manifest) so
any persisted :class:`CompressedStringStore` can be reopened writable.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time

import numpy as np

from repro.core import registry
from repro.core.api import CompressedCorpus
from repro.core.artifact import DictArtifact
from repro.core.codec import Encoder
from repro.core.index import SegmentIndex
from repro.store.drift import DriftMonitor
from repro.store.segment import SegmentedCorpus
from repro.store.store import (CompressedStringStore, device_codec,
                               write_json_atomic)


def _empty_corpus() -> CompressedCorpus:
    return CompressedCorpus(payload=np.zeros(0, dtype=np.uint8),
                            offsets=np.zeros(1, dtype=np.int64), raw_bytes=0)


def _corpus_payloads(corpus: CompressedCorpus) -> list[bytes]:
    """Per-string payload bytes via one buffer copy + slicing (cheaper than
    n ``string_payload`` calls, each of which materialises its own array)."""
    buf = corpus.payload.tobytes()
    off = corpus.offsets
    return [buf[off[i]:off[i + 1]] for i in range(corpus.n_strings)]


class MutableStringStore(CompressedStringStore):
    """Appendable store over a frozen dictionary, with drift-triggered
    compaction.

    ``corpus`` may be ``None`` to start an empty store that is populated
    purely by appends (the dictionary still comes from ``source`` — an
    artifact trained elsewhere, or a trained codec).
    """

    #: optimistic encode attempts before extend() takes the store lock for
    #: the whole encode+ingest; bounds the compact-race retry (a compact()
    #: swapping the dictionary between parse and ingest invalidates the batch)
    _MAX_ENCODE_RETRIES = 3

    def __init__(self, source, corpus: CompressedCorpus | None = None, *,
                 drift_threshold: float = 0.2, auto_compact: bool = False,
                 train_ratio: float | None = None,
                 encode_backend: str = "numpy",
                 async_seal: bool = True, **store_kw):
        # Refuse non-token-stream codecs up front with an append-specific
        # error: the tail files per-string u16 token payloads
        # (_tail_string_tokens does frombuffer("<u2")) and _tail_scan walks a
        # dictionary that raw/block codecs don't have — appends would
        # silently corrupt instead of failing here.
        self._check_token_stream(source)
        # tail state must exist before super().__init__ — the overridden
        # n_strings property can be consulted during construction
        self._tail: list[bytes] = []       # compressed payload per string
        self._tail_raw: list[int] = []     # decoded byte length per string
        self._tail_bytes = 0
        self._n_total = 0
        # reverse-lookup tail map: compressed payload -> lowest tail-local
        # id. None until the first tail locate builds it; _ingest_locked
        # then maintains it incrementally so the write path pays nothing
        # before anyone queries.
        self._tail_map: dict[bytes, int] | None = None
        if corpus is None:
            corpus = _empty_corpus()
        super().__init__(source, corpus, **store_kw)
        self._n_total = self.segments.n_strings
        if encode_backend not in ("numpy", "pallas"):
            raise ValueError(f"unknown encode_backend {encode_backend!r} "
                             "(one of 'numpy', 'pallas')")
        if encode_backend == "pallas" and device_codec() is None:
            raise ValueError("encode_backend='pallas' unavailable: "
                             "jax not importable (or REPRO_NO_JAX set)")
        self.encode_backend = encode_backend
        # frozen-dict parser; shares the compressor's already-built tables
        # (numpy) or the store's device tables (pallas, AOT-warmed here so
        # the first extend() pays no compile)
        self._encoder = self._make_encoder(self.artifact, self.compressor,
                                           self._device)
        self._encode_lock = threading.Lock()     # serialises lazy LPM rebuild
        self._io_lock = threading.RLock()        # serialises save/swap/prune
        self._dirty = False                      # unsaved appends/compacts
        base = train_ratio if train_ratio is not None else (
            corpus.ratio if corpus.compressed_bytes else None)
        self.drift = DriftMonitor(threshold=drift_threshold,
                                  baseline_ratio=base)
        self.auto_compact = auto_compact
        self.version_id = 0          # bumped by every compact()
        self.compactions = 0
        self._dir: str | None = None  # set by save()/open(): compact() target
        # ----- off-thread tail seals: a sealing extend() only *requests* a
        # seal; segment construction (join + cumsum + optional index decode)
        # runs on a background worker that commits under the lock iff the
        # tail identity it snapshotted is still current (_tail_gen guard).
        self.async_seal = bool(async_seal)
        self._sealing = False                    # worker thread active
        self._tail_gen = 0                       # bumped when the tail's
        #                                          prefix is invalidated
        self._seal_done_cv = threading.Condition(self._lock)

    @staticmethod
    def _check_token_stream(source) -> None:
        name = getattr(source, "codec", None)          # DictArtifact
        if name is None:
            obj = source[1] if isinstance(source, tuple) else source
            name = getattr(obj, "name", None)          # trained codec
        if name is None:
            return  # malformed source: super().__init__ gives the right error
        try:
            caps = registry.capabilities(name)
        except Exception:
            return  # unknown codec: super().__init__ gives the right error
        if not caps.token_stream:
            raise ValueError(
                f"MutableStringStore requires a token-stream codec: appends "
                f"file per-string u16 token payloads into the tail, but "
                f"{name!r} is not token_stream (registry capability); "
                "use a read-only CompressedStringStore for block codecs")

    def _make_encoder(self, artifact, compressor, device) -> Encoder:
        """Build (and AOT-warm) the tail encoder for the current generation.

        On the pallas backend the encoder shares the store's decode device
        when there is one (store backend jax); a numpy-store/pallas-encode
        mix builds a device from the already-packed dictionary. compact()
        calls this outside the lock so warm-up never blocks readers.
        """
        if self.encode_backend == "pallas":
            if device is None:
                device = device_codec()(compressor.dictionary,
                                        self._placement)
            enc = Encoder(artifact, backend="pallas", codec=compressor,
                          device=device)
            enc.warm()
            return enc
        return Encoder(artifact, codec=compressor)

    # -------------------------------------------------------------- tail hooks
    def _tail_n(self) -> int:
        return len(self._tail)

    def _tail_payload_bytes(self) -> int:
        return self._tail_bytes

    def _tail_string_tokens(self, local: int) -> np.ndarray:
        return np.frombuffer(self._tail[local], dtype="<u2")

    def _tail_scan(self, lo: int, hi: int) -> list[bytes]:
        if lo >= hi:
            return []
        parts = self._tail[lo:hi]
        counts = np.asarray([len(p) // 2 for p in parts], dtype=np.int64)
        tokens = np.frombuffer(b"".join(parts), dtype="<u2").astype(np.int64)
        decoded = self.dictionary.decode_tokens(tokens)
        return self._split_decoded(decoded, tokens, counts)

    def _tail_locate(self, payload: bytes) -> int | None:
        if self._tail_map is None:
            # first tail locate: build the map once; ingest maintains it
            # from here on
            m: dict[bytes, int] = {}
            for local, p in enumerate(self._tail):
                m.setdefault(p, local)
            self._tail_map = m
        return self._tail_map.get(payload)

    def _tail_prefix_hits(self, prefix, after):
        n = len(self._tail)
        if n == 0:
            return []
        sealed = self.segments.n_strings
        hits = []
        for local, s in enumerate(self._tail_scan(0, n)):
            if not s.startswith(prefix):
                continue
            gid = sealed + local
            if after is not None and (s, gid) <= after:
                continue
            hits.append((s, gid))
        hits.sort()
        return hits

    @property
    def n_strings(self) -> int:
        # a plain int read: monotonic for unlocked readers even while a seal
        # is moving strings from the tail into a new segment under the lock
        return self._n_total

    # ------------------------------------------------------ reverse lookup
    def _query_encoder(self) -> Encoder:
        # queries must parse against the exact generation the tail was
        # encoded with — share the tail encoder instead of building one
        return self._encoder

    def _encode_queries(self, strings: list[bytes]) -> list[bytes]:
        # serialise against extend()'s lazy LPM rebuild, exactly like the
        # optimistic encode pass of extend() itself
        with self._encode_lock:
            return super()._encode_queries(strings)

    # ----------------------------------------------------------------- writes
    def append(self, s: bytes) -> int:
        """Parse one string against the frozen dictionary and append it.
        Returns the new string's global id (ids are assigned contiguously)."""
        return self.extend([s])[0]

    def extend(self, strings: list[bytes]) -> list[int]:
        """Batched append: one Encoder pass, then one locked tail update."""
        strings = [bytes(s) for s in strings]
        if not strings:
            return []
        raw_lens = [len(s) for s in strings]
        ids = None
        for _ in range(self._MAX_ENCODE_RETRIES):
            with self._encode_lock:
                version = self.version_id
                encoder = self._encoder
                corpus = encoder.encode(strings)
            payloads = _corpus_payloads(corpus)
            with self._lock:
                if version == self.version_id:
                    ids = self._ingest_locked(payloads, raw_lens)
                    break
            # a compact() swapped the dictionary while we were parsing: the
            # payloads reference the OLD token table — re-parse and retry
        if ids is None:
            # retries exhausted (back-to-back auto_compact swaps): encode
            # under the store lock itself. compact()'s swap needs this lock
            # too, so the dictionary cannot change mid-parse — readers stall
            # for one batch parse, but livelock is impossible.
            with self._lock:
                corpus = self._encoder.encode(strings)
                ids = self._ingest_locked(_corpus_payloads(corpus), raw_lens)
        if self.auto_compact and self.drift.should_compact():
            self.compact()
        return ids

    def seal(self) -> None:
        """Force-seal the current tail into a (possibly short) segment.
        Joins any in-flight background seal first, then seals the remainder
        inline — on return the tail is empty."""
        with self._seal_done_cv:
            while self._sealing:
                self._seal_done_cv.wait()
            self._seal_tail_locked()

    def seal_barrier(self) -> None:
        """Block until no background seal is pending: afterwards the tail
        is strictly shorter than ``strings_per_segment`` (until the next
        sealing extend). compact() and save() call this so their snapshots
        never race a half-built segment."""
        with self._seal_done_cv:
            while self._sealing:
                self._seal_done_cv.wait()

    def _ingest_locked(self, payloads: list[bytes], raw_lens: list[int],
                       assign_ids: bool = True) -> list[int]:
        """``assign_ids=False`` re-files payloads whose ids are already
        published (compact's delta re-parse) without touching ``_n_total``.

        Group-commit: the whole batch appends to the tail with one drift
        observation (DriftMonitor explicitly accepts per-batch observation)
        — no per-string Python loop on the hot write path. Crossing a seal
        boundary only *requests* sealing: the background worker builds the
        segment off-thread (``async_seal=False`` restores inline seals).
        """
        self._dirty = True
        n = len(payloads)
        ids = list(range(self._n_total, self._n_total + n)) if assign_ids else []
        if self._tail_map is not None:
            start = len(self._tail)
            for j, p in enumerate(payloads):
                self._tail_map.setdefault(p, start + j)
        self._tail.extend(payloads)
        self._tail_raw.extend(raw_lens)
        comp = sum(map(len, payloads))
        self._tail_bytes += comp
        self.drift.observe(sum(raw_lens), comp)
        if assign_ids:
            self._n_total += n
        spc = self.segments.strings_per_segment
        if len(self._tail) >= spc:
            if self.async_seal:
                self._request_seal_locked()
            else:
                while len(self._tail) >= spc:
                    self._seal_tail_locked(spc)
        return ids

    def _seal_tail_locked(self, k: int | None = None) -> None:
        """Seal the first ``k`` tail strings (all of them when None) into a
        segment, inline under the lock."""
        n = len(self._tail)
        k = n if k is None else min(k, n)
        if k == 0:
            return
        parts = self._tail[:k]
        offsets = np.zeros(k + 1, dtype=np.int64)
        np.cumsum([len(p) for p in parts], out=offsets[1:])
        payload = np.frombuffer(b"".join(parts), dtype=np.uint8)
        # once anyone has issued a reverse lookup, keep the index current:
        # build the new segment's index at seal time (tail decoded before
        # it is cleared). Stores nobody locates in never pay this decode.
        raw = (self._tail_scan(0, k)
               if (self._seg_indexes or self._tail_map is not None)
               else None)
        self._commit_seal_locked(k, payload, offsets,
                                 sum(self._tail_raw[:k]), raw)

    def _commit_seal_locked(self, k: int, payload: np.ndarray,
                            offsets: np.ndarray, raw_bytes: int,
                            raw: list[bytes] | None) -> None:
        """Append the built segment and drop the first ``k`` tail strings.
        Bumps ``_tail_gen``: any other in-flight seal snapshot of the old
        tail prefix is now stale and must abandon its commit."""
        self.segments.append_segment(payload, offsets, raw_bytes=raw_bytes)
        if raw is not None:
            seg = self.segments.segments[-1]
            self._seg_indexes[seg.index] = SegmentIndex.build(
                seg.payload, seg.offsets, raw)
        del self._tail[:k]
        del self._tail_raw[:k]
        self._tail_bytes -= int(offsets[-1])
        if self._tail_map is not None:
            # a partial seal shifts every remaining tail-local id
            m: dict[bytes, int] = {}
            for local, p in enumerate(self._tail):
                m.setdefault(p, local)
            self._tail_map = m
        self._tail_gen += 1

    def _request_seal_locked(self) -> None:
        if self._sealing:
            return  # worker already draining; it re-checks the boundary
        self._sealing = True
        threading.Thread(target=self._seal_worker, daemon=True,
                         name="repro-seal").start()

    def _seal_worker(self) -> None:
        """Drain the tail down below the seal boundary, one segment per
        iteration. Each round snapshots the first ``spc`` payloads under
        the lock, builds the segment arrays (and the optional reverse-index
        decode) OFF the lock, and commits only if neither a compaction
        (version_id) nor a competing seal/swap (_tail_gen) invalidated the
        snapshot meanwhile."""
        while True:
            with self._lock:
                spc = self.segments.strings_per_segment
                if len(self._tail) < spc:
                    self._sealing = False
                    self._seal_done_cv.notify_all()
                    return
                version, gen = self.version_id, self._tail_gen
                parts = self._tail[:spc]
                raw_bytes = sum(self._tail_raw[:spc])
                need_raw = bool(self._seg_indexes) \
                    or self._tail_map is not None
                dictionary = self.dictionary
            offsets = np.zeros(spc + 1, dtype=np.int64)
            np.cumsum([len(p) for p in parts], out=offsets[1:])
            payload = np.frombuffer(b"".join(parts), dtype=np.uint8)
            raw = (self._decode_payloads(parts, dictionary)
                   if need_raw else None)
            with self._lock:
                if self.version_id != version or self._tail_gen != gen:
                    continue  # snapshot went stale: re-evaluate from scratch
                self._commit_seal_locked(spc, payload, offsets,
                                         raw_bytes, raw)

    @staticmethod
    def _decode_payloads(parts: list[bytes], dictionary) -> list[bytes]:
        """Decode token-stream payloads against a *captured* dictionary
        (the seal worker must not read self.dictionary off-lock)."""
        counts = np.asarray([len(p) // 2 for p in parts], dtype=np.int64)
        tokens = np.frombuffer(b"".join(parts), dtype="<u2").astype(np.int64)
        decoded = dictionary.decode_tokens(tokens)
        tok_lens = dictionary.lens[tokens].astype(np.int64)
        byte_cum = np.zeros(tokens.size + 1, dtype=np.int64)
        np.cumsum(tok_lens, out=byte_cum[1:])
        bounds = byte_cum[np.concatenate(([0], np.cumsum(counts)))]
        return [decoded[int(bounds[i]):int(bounds[i + 1])]
                for i in range(len(counts))]

    # ------------------------------------------------------------- compaction
    def compact(self, *, sample_strings: int | None = None,
                dir_path: str | None = None, prune_old: bool = True) -> dict:
        """Re-train the dictionary on (a sample of) the live data, re-encode
        every live string, and atomically swap the store's state.

        Training and bulk re-encoding run *outside* the store lock — reads
        and appends keep being served from the old state; strings appended
        meanwhile are re-parsed against the new dictionary during the final
        locked swap. When the store is directory-backed (``save``/``open``),
        the rewrite lands in a new ``v{n+1}`` subdirectory and the
        ``current.json`` manifest is swapped atomically; stale version
        directories are pruned afterwards (``prune_old=False`` keeps them).
        """
        t0 = time.perf_counter()
        self.seal_barrier()  # never snapshot a half-built background segment
        n0 = self.n_strings
        # decode the live data in per-segment lock windows — ids < n0 are
        # immutable, so chunked reads see the same bytes as one big scan
        # while concurrent reads/appends keep interleaving
        live: list[bytes] = []
        chunk = max(1, self.segments.strings_per_segment)
        for lo in range(0, n0, chunk):
            with self._lock:
                live.extend(self._scan_locked(lo, min(lo + chunk, n0)))
        if not live:
            return {"n_strings": 0, "ratio_before": 0.0, "ratio_after": 0.0,
                    "train_s": 0.0, "total_s": 0.0,
                    "version": self._version_name(), "dir": self._dir}
        raw = sum(len(s) for s in live)
        with self._lock:
            compressed_before = (self.segments.payload_bytes
                                 + self._tail_bytes)
        ratio_before = raw / max(1, compressed_before)

        # re-train on a sample of live data (the codec's own sample_bytes
        # cap still applies inside train())
        sample = live
        if sample_strings is not None and sample_strings < len(live):
            step = max(1, len(live) // sample_strings)
            sample = live[::step][:sample_strings]
        new_comp = registry.codec_from_artifact(self.artifact)
        t_train0 = time.perf_counter()
        new_comp.train(sample)
        train_s = time.perf_counter() - t_train0
        new_corpus = new_comp.compress(live)
        # artifact freeze and device-table upload both happen OUTSIDE the
        # lock — the locked swap only assigns
        new_artifact = new_comp.to_artifact()
        new_device = self._new_device(new_comp.dictionary)
        # tail encoder for the new generation — built (and, on the pallas
        # backend, AOT-warmed) outside the lock like the device tables
        new_encoder = self._make_encoder(new_artifact, new_comp, new_device)

        with self._lock:
            # strings appended while we were retraining: decode them from
            # the old state, then re-parse against the new dictionary. Their
            # ids are already published, so _n_total never moves — lock-free
            # n_strings readers stay monotonic through the whole swap
            delta = self._scan_locked(n0, self._n_total)
            self._swap_state_locked(new_comp, new_corpus, new_artifact,
                                    new_device, new_encoder)
            if delta:
                d_corpus = new_comp.compress(delta)
                self._ingest_locked(
                    [d_corpus.string_payload(i) for i in range(len(delta))],
                    [len(s) for s in delta], assign_ids=False)
            compressed_after = self.segments.payload_bytes + self._tail_bytes
        self.compactions += 1

        target = dir_path or self._dir
        old_version = f"v{self.version_id - 1:04d}"
        if target is not None:
            # one holder writes the directory at a time: a concurrent save()
            # must not recreate (or point the manifest at) the generation
            # this prune is deleting
            with self._io_lock:
                self.save(target)  # writes v{id}/ then swaps current.json
                if prune_old:
                    shutil.rmtree(os.path.join(target, old_version),
                                  ignore_errors=True)
        raw_total = raw + sum(len(s) for s in delta)
        return {"n_strings": self.n_strings,
                "ratio_before": round(ratio_before, 4),
                "ratio_after": round(raw_total / max(1, compressed_after), 4),
                "train_s": round(train_s, 4),
                "total_s": round(time.perf_counter() - t0, 4),
                "version": f"v{self.version_id:04d}",
                "dir": target}

    def _swap_state_locked(self, compressor, corpus: CompressedCorpus,
                           artifact: DictArtifact | None = None,
                           device=None, encoder: Encoder | None = None) -> None:
        """Replace dictionary + corpus + segments in one locked step. Decoded
        values are unchanged byte-for-byte, but cached entries belong to the
        rewritten segments' old token streams — drop them all. Pass the
        pre-frozen ``artifact`` so the token table is not re-serialized
        while every reader and writer is blocked on the lock."""
        self.compressor = compressor
        self._artifact = artifact           # re-frozen lazily when None
        self.dictionary = compressor.dictionary
        self.corpus = corpus
        self.segments = SegmentedCorpus.from_corpus(
            corpus, self.segments.strings_per_segment)
        self._set_bucket_caps(self._string_rows(corpus))
        self._device = (device if device is not None
                        else self._new_device(self.dictionary))
        self._encoder = (encoder if encoder is not None else
                         self._make_encoder(self.artifact, self.compressor,
                                            self._device))
        self._dirty = True
        self._tail = []
        self._tail_raw = []
        self._tail_bytes = 0
        # reverse-lookup state is generation-scoped: fingerprints index the
        # *encoded* forms, which the rewrite just changed wholesale
        self._seg_indexes = {}
        self._tail_map = None
        self._locate_encoder = None
        # _n_total is deliberately NOT reset: acknowledged ids must never
        # un-publish, and the caller re-files any delta beyond the corpus
        self.cache.clear()
        self.drift.reset(corpus.ratio if corpus.compressed_bytes else None)
        if self.tier is not None:
            # cold state is segment-scoped: the rewrite folded every cold
            # segment's data back into the new (hot) generation
            self.tier.clear_locked()
        self._tail_gen += 1   # in-flight seal snapshots are now stale
        self.version_id += 1

    # ------------------------------------------------------------- persistence
    def _version_name(self) -> str:
        return f"v{self.version_id:04d}"

    def snapshot_corpus(self) -> CompressedCorpus:
        with self._lock:
            return self._to_corpus_locked()

    def _to_corpus_locked(self) -> CompressedCorpus:
        """One flat CompressedCorpus over sealed segments + unsealed tail."""
        parts = [s.payload for s in self.segments.segments]
        parts += [np.frombuffer(p, dtype=np.uint8) for p in self._tail]
        payload = (np.concatenate(parts) if parts
                   else np.zeros(0, dtype=np.uint8))
        offs = [np.zeros(1, dtype=np.int64)]
        base = 0
        for seg in self.segments.segments:
            if seg.n_strings:
                offs.append(seg.offsets[1:] + base)
            base += seg.payload_bytes
        for p in self._tail:
            base += len(p)
            offs.append(np.asarray([base], dtype=np.int64))
        raw = self.segments.raw_bytes + sum(self._tail_raw)
        return CompressedCorpus(payload=payload,
                                offsets=np.concatenate(offs),
                                raw_bytes=int(raw),
                                meta={"compressor": self.compressor.name})

    def save(self, dir_path: str) -> None:
        """Write the current dictionary generation as ``<dir>/v{id}/`` (flat
        store layout, tail included in the corpus) and atomically point the
        ``current.json`` manifest at it.

        Dictionary, corpus, version name and meta are all snapshotted in ONE
        locked section — a compact() landing mid-save must never pair the
        new dictionary with the old generation's corpus on disk — and the
        whole snapshot+write sequence holds the IO lock, so it serialises
        against compact()'s own save+prune (a stale generation is never
        recreated after its prune, and the manifest never points backwards).
        """
        self.seal_barrier()  # the snapshot below must see a settled tail
        with self._io_lock:
            self._save_io_locked(dir_path)

    def _save_io_locked(self, dir_path: str) -> None:
        with self._lock:
            vname = self._version_name()
            artifact = self.artifact
            corpus = self._to_corpus_locked()
            meta = self.store_meta(
                mutable=True, n_tail=len(self._tail),
                version_id=self.version_id,
                encode_backend=self.encode_backend,
                async_seal=self.async_seal,
                train_ratio=self.drift.baseline_ratio,
                drift_raw_bytes=self.drift.raw_bytes,
                drift_compressed_bytes=self.drift.compressed_bytes,
                drift_observations=self.drift.observations,
                drift_threshold=self.drift.threshold,
                **self._tier_meta_locked())
            manifest = {"format_version": 1, "current": vname,
                        "codec": artifact.codec, "n_strings": self.n_strings,
                        "compactions": self.compactions}
            # captured in the same locked snapshot as the corpus: the
            # sidecar on disk must describe exactly the segments it sits
            # next to
            index_blob = self._dump_index_locked()
            # cleared HERE, inside the snapshot's locked section: an append
            # landing while the files below are written re-marks the store
            # dirty and is not covered by this snapshot
            self._dirty = False
        sub = os.path.join(dir_path, vname)
        os.makedirs(sub, exist_ok=True)
        artifact.save(os.path.join(sub, self._DICT_FILE))
        corpus.save(os.path.join(sub, self._CORPUS_FILE))
        write_json_atomic(os.path.join(sub, self._META_FILE), meta)
        if index_blob is not None:
            with open(os.path.join(sub, self._INDEX_FILE), "wb") as f:
                f.write(index_blob)
        if meta.get("cold_segments"):
            # the cold containers are immutable once written, so copying
            # them after the snapshot's lock dropped cannot tear
            self.tier.copy_cold_files(meta["cold_segments"], sub)
        write_json_atomic(os.path.join(dir_path, self._CURRENT_FILE),
                          manifest)
        # when upgrading a plain (flat) store directory to the versioned
        # layout, drop the superseded flat files: a reader must never find
        # two generations disagreeing in one directory
        stale_names = [self._DICT_FILE, self._CORPUS_FILE, self._META_FILE,
                       self._INDEX_FILE]
        stale_names += [n for n in os.listdir(dir_path)
                        if n.startswith("cold-") and n.endswith(".rlz")]
        for name in stale_names:
            stale = os.path.join(dir_path, name)
            if os.path.exists(stale):
                os.remove(stale)
        self._dir = dir_path

    @classmethod
    def open(cls, dir_path: str, mmap: bool = True,
             **overrides) -> "MutableStringStore":
        """Reopen a mutable store: versioned layout (``current.json``) or a
        plain read-only store directory. An unsealed tail saved with the
        corpus is split back out so appends keep sealing on the same
        boundaries."""
        sub = cls._resolve_current(dir_path)
        with open(os.path.join(sub, cls._META_FILE)) as f:
            meta = json.load(f)
        artifact = DictArtifact.load(os.path.join(sub, cls._DICT_FILE),
                                     mmap=mmap)
        corpus = CompressedCorpus.load(os.path.join(sub, cls._CORPUS_FILE),
                                       mmap=mmap)
        n, n_tail = corpus.n_strings, int(meta.get("n_tail", 0))
        sealed = corpus.slice_strings(0, n - n_tail) if n_tail else corpus
        kw = {k: meta[k] for k in cls._STORE_KW}
        kw["train_ratio"] = meta.get("train_ratio")
        kw["drift_threshold"] = meta.get("drift_threshold", 0.2)
        # a pallas encoder saved here raises in the constructor where the
        # kernels are unavailable: reopen with encode_backend="numpy" to
        # serve such a store from a numpy-only host
        kw["encode_backend"] = meta.get("encode_backend", "numpy")
        kw["async_seal"] = meta.get("async_seal", True)
        kw.update(overrides)  # caller overrides beat every saved param
        store = cls(artifact, sealed, **kw)
        if n_tail:
            lens = store.dictionary.lens
            payloads, raws = [], []
            for i in range(n - n_tail, n):
                toks = np.asarray(corpus.string_tokens(i), dtype=np.int64)
                payloads.append(corpus.string_payload(i))
                raws.append(int(lens[toks].astype(np.int64).sum()))
            with store._lock:
                store._ingest_locked(payloads, raws)
        # restore the drift window exactly as saved (the tail re-ingest above
        # re-observed only the tail; overwrite with the persisted counters)
        if "drift_raw_bytes" in meta:
            store.drift.raw_bytes = int(meta["drift_raw_bytes"])
            store.drift.compressed_bytes = int(meta["drift_compressed_bytes"])
            store.drift.observations = int(meta["drift_observations"])
        store.version_id = int(meta.get("version_id", 0))
        store._load_index(sub)
        store._attach_tier(sub, meta)
        store._dir = dir_path
        store._dirty = False   # tail restore above is not an unsaved append
        return store

    # ------------------------------------------------------------------ stats
    def stats_snapshot(self) -> dict:
        snap = super().stats_snapshot()
        snap.update(drift=self.drift.snapshot(), compactions=self.compactions,
                    version=self._version_name())
        return snap
