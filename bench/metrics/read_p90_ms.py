"""The 90th percentile of every request's latency over the window, on the
client's clock (layer: client). It is the end-to-end ``read_p90_ms`` of a
cell whose runs spread too widely to hold that tail to a bound; here it
has none, and a failed request lies beyond every limit, as there."""

import sys


def read(ctx):
    return min(ctx["latency"]["p90_ms"], sys.float_info.max)
