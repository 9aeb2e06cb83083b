"""Ids per read RPC: how many lookups the client's and the service's
coalescing put into one request on the wire (layer: RPC / service).

Reads the stats RPC's counter deltas over the window: the store's
``lookups`` over the server's ``multiget`` and ``get`` request counts.
"""


def read(ctx):
    c = ctx["counters"]
    rpcs = c["rpc_multiget"] + c["rpc_get"]
    return c["lookups"] / rpcs if rpcs else None
