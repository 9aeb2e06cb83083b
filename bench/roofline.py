"""The decode's work as the algorithm needs it, and the chip's peaks.

Decoding a string reads its u16 token ids, one 16-byte dictionary row per
token, and writes its bytes. That is the useful traffic, whatever the
kernel does with padding or wider types, so the roofline share of every
implementation is measured against the same bytes. Decode does no
arithmetic to speak of: it is bound by memory bandwidth.
"""

from __future__ import annotations

import json
import os

from bench.layout import ROOT

#: bytes of one token id as the format stores it (u16)
TOKEN_ID_BYTES = 2
#: bytes of one dictionary row the decode gathers per token (OnPair16)
DICT_ROW_BYTES = 16


def decode_useful_bytes(real_tokens: int, decoded_bytes: int) -> int:
    """Bytes the decode of ``real_tokens`` tokens into ``decoded_bytes``
    output bytes must move: ids in, one dictionary row per token, bytes out."""
    return (real_tokens * (TOKEN_ID_BYTES + DICT_ROW_BYTES)
            + int(decoded_bytes))


def peaks(device_kind: str, root: str = ROOT) -> dict:
    """The peaks of ``device_kind`` from ``bench/peaks.json``; a kind that
    is not in the table is an error, never a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def bandwidth_share(nbytes: int, seconds: float, bytes_per_s: float
                    ) -> float | None:
    """Percent of the peak bandwidth that ``nbytes`` in ``seconds`` reach;
    None where nothing ran."""
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (seconds * bytes_per_s)
