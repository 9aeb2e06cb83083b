"""Milliseconds by which the slowest shard call of a fanned-out client
multiget trails the fastest (layer: router fan-out, ``net/router.py``
``DistributedStringStore``): the mean, over every multiget of the window
that went to two shards or more, of its slowest shard call less its
fastest, on the client's host clock (``bench/fanout.py``). None where no
multiget fanned out."""


def read(ctx):
    fan = ctx.get("fanout")
    if not fan or not fan["slowest_s"]:
        return None
    gaps = [a - b for a, b in zip(fan["slowest_s"], fan["fastest_s"])]
    return 1e3 * sum(gaps) / len(gaps)
