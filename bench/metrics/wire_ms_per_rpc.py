"""Milliseconds per multiget RPC outside the server's handling of it
(layer: RPC and service, ``net/router.py``, ``net/shard_server.py``): the
client process's ``rpc.multiget`` seconds less the server process's
``server.multiget`` seconds, over the ``rpc.multiget`` count: framing,
sockets and the two processes' scheduling. None where the program records
no such spans."""


def read(ctx):
    spans = ctx.get("spans") or {}
    rpc, served = spans.get("rpc.multiget"), spans.get("server.multiget")
    if not rpc or not rpc["count"] or not served:
        return None
    return 1e3 * (rpc["seconds"] - served["seconds"]) / rpc["count"]
