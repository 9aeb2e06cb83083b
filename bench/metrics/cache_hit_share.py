"""Share of cache probes that hit the decoded-string LRU (layer: store
cache), from the stats RPC's cache hit and miss deltas over the window."""


def read(ctx):
    c = ctx["counters"]
    probes = c["cache_hits"] + c["cache_misses"]
    return 100.0 * c["cache_hits"] / probes if probes else None
