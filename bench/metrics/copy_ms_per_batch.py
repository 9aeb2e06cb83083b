"""Host milliseconds around each device decode batch (layer: host-device
copy): the self time of the program's ``kernel.pack``, ``kernel.h2d``,
``kernel.dispatch``, ``kernel.d2h`` and ``kernel.unpack`` spans over the
``kernel.decode_batch`` count, from ``ctx["spans"]`` (window deltas, both
processes merged). The device wait (``kernel.wait``) is left out. None
where the program records no such spans."""

COPY_SPANS = ("kernel.pack", "kernel.h2d", "kernel.dispatch", "kernel.d2h",
              "kernel.unpack")


def read(ctx):
    spans = ctx.get("spans") or {}
    batches = spans.get("kernel.decode_batch", {}).get("count")
    if not batches:
        return None
    own = sum(spans.get(n, {}).get("self_seconds", 0.0) for n in COPY_SPANS)
    return 1e3 * own / batches
