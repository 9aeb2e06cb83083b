"""The benchmark's own copy of the corpus generators.

Same structure as the program's synthetic analogues of the paper's datasets
(``urls``: few domains, zipf path segments, id and query suffixes;
``book_titles``: zipf pseudo-words with series prefixes and edition
suffixes), drawn in bulk with numpy so that a 128 MiB corpus takes seconds.
Each string is a row of piece ids into a small table of byte strings, and
:func:`assemble` concatenates the pieces of every row at once. Deterministic
in ``(seed, target_bytes)``: strings are appended until their total reaches
``target_bytes``. The vocabulary (domains, path segments, words) is drawn
from the fixed :data:`VOCAB_SEED`, and ``seed`` draws the rows: every seed
gives a corpus of the same dataset, with the same length distribution. The
yardstick lives here so that it does not move when the program's own
generator does.
"""

from __future__ import annotations

import numpy as np

_CONSONANTS = np.frombuffer(b"bcdfghjklmnpqrstvwz", dtype=np.uint8)
_VOWELS = np.frombuffer(b"aeiou", dtype=np.uint8)
#: rows drawn per block before trimming to the target size
_BLOCK = 1 << 17
#: rows assembled per numpy pass (bounds the byte-index array)
_ASSEMBLE_ROWS = 1 << 16
#: seed of each dataset's vocabulary, which is part of the dataset
VOCAB_SEED = 0


def word_vocab(rng: np.random.Generator, n: int, min_syl: int,
               max_syl: int) -> list[bytes]:
    """Pronounceable pseudo-words of CV(C) syllables."""
    syl = rng.integers(min_syl, max_syl + 1, size=n)
    total = int(syl.sum())
    cons = rng.choice(_CONSONANTS, size=total)
    vow = rng.choice(_VOWELS, size=total)
    extra = rng.random(total) < 0.3
    tail = rng.choice(_CONSONANTS, size=total)
    sylls = [bytes((c, v, t)) if e else bytes((c, v))
             for c, v, t, e in zip(cons.tolist(), vow.tolist(),
                                   tail.tolist(), extra.tolist())]
    bounds = np.concatenate(([0], np.cumsum(syl))).tolist()
    return [b"".join(sylls[a:b]) for a, b in zip(bounds[:-1], bounds[1:])]


def _zeta(a: float, m: int = 1 << 16) -> float:
    """Riemann zeta of ``a`` > 1: a partial sum plus the Euler-Maclaurin
    tail, exact to float64 rounding for the exponents used here."""
    head = float(np.sum(np.arange(1, m, dtype=np.float64) ** -a))
    return head + m ** (1 - a) / (a - 1) + 0.5 * m ** -a + a * m ** (-a - 1) / 12


def zipf_cdf(n_vocab: int, a: float = 1.15) -> np.ndarray:
    """CDF of ``min(zipf(a) - 1, n_vocab - 1)``: zipf ranks clipped into
    ``[0, n_vocab)``, the mass beyond the last rank on the last index."""
    pmf = np.arange(1, n_vocab, dtype=np.float64) ** -a / _zeta(a)
    return np.append(np.cumsum(pmf), 1.0)


def zipf_indices(rng: np.random.Generator, cdf: np.ndarray,
                 size) -> np.ndarray:
    """Indices drawn by inverse transform from ``cdf``."""
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      cdf.size - 1)


def assemble(table: list[bytes], pieces: np.ndarray) -> list[bytes]:
    """Concatenate each row's pieces (ids into ``table``; -1 = none)."""
    lens = np.array([len(t) for t in table], dtype=np.int64)
    starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
    blob = np.frombuffer(b"".join(table), dtype=np.uint8)
    out: list[bytes] = []
    for r0 in range(0, pieces.shape[0], _ASSEMBLE_ROWS):
        rows = pieces[r0:r0 + _ASSEMBLE_ROWS]
        ids = rows[rows >= 0]
        plen = lens[ids]
        ends = np.cumsum(plen)
        # output byte k of piece j reads blob[starts[id_j] + k - out_start_j]
        idx = np.repeat(starts[ids] - (ends - plen), plen)
        idx += np.arange(int(ends[-1]) if ends.size else 0, dtype=np.int64)
        buf = blob[idx].tobytes()
        row_len = np.where(rows >= 0, lens[np.maximum(rows, 0)], 0).sum(1)
        cut = np.concatenate(([0], np.cumsum(row_len))).tolist()
        out.extend(buf[a:b] for a, b in zip(cut[:-1], cut[1:]))
    return out


def _take_until(blocks: list[tuple[np.ndarray, np.ndarray]],
                target_bytes: int) -> np.ndarray:
    """Stack piece blocks and keep rows up to the one whose running total
    first reaches ``target_bytes``."""
    pieces = np.concatenate([p for p, _ in blocks])
    total = np.cumsum(np.concatenate([n for _, n in blocks]))
    return pieces[:int(np.searchsorted(total, target_bytes)) + 1]


def _row_bytes(pieces: np.ndarray, lens: np.ndarray) -> np.ndarray:
    return np.where(pieces >= 0, lens[np.maximum(pieces, 0)], 0).sum(1)


def urls(target_bytes: int, seed: int, path_depth=(2, 6)) -> list[bytes]:
    """URL analogue: ``https://www.<word><tld>/<seg>/.../<seg>`` with a
    zipf domain and ``path_depth`` (least, most) zipf path segments; 35%
    end in ``/item_id_%06d``, 15% in ``?page=%d&ref=<seg>``."""
    lo, hi = (int(d) for d in path_depth)
    vrng = np.random.default_rng(VOCAB_SEED)
    tlds = vrng.choice(np.array([b".com", b".org", b".net", b".io"]), 120)
    domains = [b"https://www." + w + bytes(t)
               for w, t in zip(word_vocab(vrng, 120, 2, 4), tlds)]
    segs = word_vocab(vrng, 600, 2, 4)
    dom_cdf, seg_cdf = zipf_cdf(len(domains)), zipf_cdf(len(segs))
    rng = np.random.default_rng(seed)
    digits = [b"%d" % d for d in range(10)]
    pages = [b"%d" % p for p in range(50)]
    table = (domains + [b"/" + s for s in segs] + segs + digits + pages
             + [b"/item_id_", b"?page=", b"&ref="])
    o_slash, o_seg = len(domains), len(domains) + len(segs)
    o_dig = o_seg + len(segs)
    o_page = o_dig + 10
    item, page, ref = o_page + 50, o_page + 51, o_page + 52
    lens = np.array([len(t) for t in table], dtype=np.int64)
    sfx = hi + 1          # first suffix column
    blocks, total = [], 0
    while total < target_bytes:
        n = _BLOCK
        p = np.full((n, sfx + 7), -1, dtype=np.int64)
        p[:, 0] = zipf_indices(rng, dom_cdf, n)
        depth = rng.integers(lo, hi + 1, size=n)
        path = o_slash + zipf_indices(rng, seg_cdf, (n, hi))
        p[:, 1:sfx] = np.where(np.arange(hi) < depth[:, None], path, -1)
        r = rng.random(n)
        item_id = rng.integers(0, 1000000, size=n)
        pg = rng.integers(0, 50, size=n)
        ref_seg = rng.integers(0, len(segs), size=n)
        is_item, is_page = r < 0.35, (r >= 0.35) & (r < 0.5)
        # the suffix goes right after the last path piece: the last seven
        # columns hold it for every row, and unused path columns stay -1
        id_digits = (item_id[:, None] // 10 ** np.arange(5, -1, -1)) % 10
        p[is_item, sfx] = item
        p[is_item, sfx + 1:sfx + 7] = o_dig + id_digits[is_item]
        p[is_page, sfx] = page
        p[is_page, sfx + 1] = o_page + pg[is_page]
        p[is_page, sfx + 2] = ref
        p[is_page, sfx + 3] = o_seg + ref_seg[is_page]
        nbytes = _row_bytes(p, lens)
        blocks.append((p, nbytes))
        total += int(nbytes.sum())
    return assemble(table, _take_until(blocks, target_bytes))


def book_titles(target_bytes: int, seed: int, words=(3, 9)) -> list[bytes]:
    """Book-title analogue: ``words`` (least, most) zipf pseudo-words, 70%
    capitalised; 15% carry a ``The <Series>: `` prefix, 10% a ``(Vol. n)``
    suffix, 7% a ``- Special Edition`` suffix."""
    lo, hi = (int(w) for w in words)
    vrng = np.random.default_rng(VOCAB_SEED)
    vocab = word_vocab(vrng, 4000, 1, 4)
    series = [b"The " + w.capitalize() + b": "
              for w in word_vocab(vrng, 50, 2, 3)]
    word_cdf = zipf_cdf(len(vocab))
    rng = np.random.default_rng(seed)
    caps = [w.capitalize() for w in vocab]
    vols = [b" (Vol. %d)" % v for v in range(1, 30)]
    # word k of a title: [raw, capitalised] x [first, later (leading space)]
    table = (vocab + caps + [b" " + w for w in vocab]
             + [b" " + w for w in caps] + series + vols
             + [b" - Special Edition"])
    nv = len(vocab)
    o_series = 4 * nv
    o_vol = o_series + len(series)
    special = o_vol + len(vols)
    lens = np.array([len(t) for t in table], dtype=np.int64)
    blocks, total = [], 0
    while total < target_bytes:
        n = _BLOCK
        p = np.full((n, hi + 2), -1, dtype=np.int64)
        nw = rng.integers(lo, hi + 1, size=n)
        ws = zipf_indices(rng, word_cdf, (n, hi))
        ws += nv * (rng.random((n, hi)) < 0.7)     # capitalised variant
        ws[:, 1:] += 2 * nv                         # leading space
        p[:, 1:hi + 1] = np.where(np.arange(hi) < nw[:, None], ws, -1)
        r = rng.random(n)
        ser = rng.integers(0, len(series), size=n)
        vol = rng.integers(0, len(vols), size=n)
        p[:, 0] = np.where(r < 0.15, o_series + ser, -1)
        p[:, hi + 1] = np.where((r >= 0.15) & (r < 0.25), o_vol + vol,
                                np.where((r >= 0.25) & (r < 0.32), special,
                                         -1))
        nbytes = _row_bytes(p, lens)
        blocks.append((p, nbytes))
        total += int(nbytes.sum())
    return assemble(table, _take_until(blocks, target_bytes))


DATASETS = {"urls": urls, "book_titles": book_titles}


def generate(name: str, target_bytes: int, seed: int,
             shape: dict | None = None) -> list[bytes]:
    """``target_bytes`` of dataset ``name`` from ``seed``; ``shape`` holds
    the generator's length parameters (a configuration's
    ``dataset_shape``)."""
    if name not in DATASETS:
        raise ValueError(f"unknown dataset {name!r}; known: {sorted(DATASETS)}")
    return DATASETS[name](target_bytes, seed, **(shape or {}))
