"""Milliseconds a get waits in the client's batcher before a drain takes it
(layer: client, ``client/session.py`` ``_GetBatcher``): the program's
``client.coalesce`` seconds over its count. None where the program records
no such span."""


def read(ctx):
    got = (ctx.get("spans") or {}).get("client.coalesce")
    if not got or not got["count"]:
        return None
    return 1e3 * got["seconds"] / got["count"]
