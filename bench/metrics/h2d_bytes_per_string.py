"""Bytes copied to the device per string the kernel decoded (layer:
host-device copy, ``kernels/ops.py`` ``decode_batch``), from the store's
``h2d_bytes`` and ``device_strings`` deltas over the window: the padded
token matrix and its counts, so padding shows here. None where the program
does not count them, or no string reached the kernel."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("device_strings") or "h2d_bytes" not in c:
        return None
    return c["h2d_bytes"] / c["device_strings"]
