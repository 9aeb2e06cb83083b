"""Host microseconds of the store's own work per lookup (layer: store host
prep, ``store/store.py``): the self time of the program's
``store.multiget`` (range checks, cache probes), ``store.decode``,
``store.tokens`` (per-miss token lists) and ``store.cache_put`` spans over
the store's ``lookups`` delta. None where the program records no such
spans."""

PREP_SPANS = ("store.multiget", "store.decode", "store.tokens",
              "store.cache_put")


def read(ctx):
    spans = ctx.get("spans") or {}
    lookups = ctx["counters"].get("lookups")
    if not lookups or "store.multiget" not in spans:
        return None
    own = sum(spans.get(n, {}).get("self_seconds", 0.0) for n in PREP_SPANS)
    return 1e6 * own / lookups
