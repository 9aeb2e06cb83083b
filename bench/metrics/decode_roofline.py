"""Decode kernel's share of the HBM roofline (layer: kernel).

The useful bytes come from counts, not from the kernel's padded shapes:
u16 ids in, one 16-byte dictionary row per real token, decoded bytes out
(``bench.roofline.decode_useful_bytes``), summed over every shard. The
time is the device's non-transfer compute time in the traced window, not
filtered by kernel name, summed over the chips: the useful bytes of all
chips over their chip-seconds of compute times one chip's bandwidth. Decode
is a gather, so the bandwidth bound is the one that applies.
"""

from bench.roofline import bandwidth_share, decode_useful_bytes


def read(ctx):
    c, trace = ctx["counters"], ctx["trace"]
    if trace is None or not c["real_tokens"]:
        return None
    nbytes = decode_useful_bytes(c["real_tokens"], c["decoded_bytes"])
    return bandwidth_share(nbytes, sum(trace["compute_s_per_device"]),
                           ctx["peaks"]["hbm_bytes_per_s"])
