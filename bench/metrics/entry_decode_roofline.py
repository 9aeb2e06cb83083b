"""Decode's share of the HBM roofline for dictionary entries of any length
(layer: kernel).

The useful bytes are the algorithm's work, whatever implements it: the u16
ids in, each token's entry bytes read from the dictionary, and the bytes
out. The entry bytes read are the decoded bytes, so the count is
``2 * real_tokens + 2 * decoded_bytes``, summed over every shard.
``decode_roofline`` counts one 16-byte row read per token instead, which
undercounts an entry longer than 16 B; the two counts agree only where
every entry decoded is 16 B long. The time is the same as there: the
device's non-transfer compute seconds in the traced window, summed over the
chips, times one chip's HBM bandwidth.
"""

from bench.roofline import TOKEN_ID_BYTES, bandwidth_share


def entry_useful_bytes(real_tokens: int, decoded_bytes: int) -> int:
    """Bytes the decode of ``real_tokens`` tokens into ``decoded_bytes``
    output bytes must move: ids in, every entry byte read, bytes out."""
    return real_tokens * TOKEN_ID_BYTES + 2 * int(decoded_bytes)


def read(ctx):
    c, trace = ctx["counters"], ctx["trace"]
    if trace is None or not c["real_tokens"]:
        return None
    nbytes = entry_useful_bytes(c["real_tokens"], c["decoded_bytes"])
    return bandwidth_share(nbytes, sum(trace["compute_s_per_device"]),
                           ctx["peaks"]["hbm_bytes_per_s"])
