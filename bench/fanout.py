"""The router's fan-out on the host clock: how far apart the shard calls
of one client multiget finish.

``repro.net.router.DistributedStringStore`` splits a multiget into one RPC
per shard and waits for all of them, so the slowest shard call sets the
multiget's time. :class:`FanoutClock` wraps a router's
``_shard_multiget`` and ``_fanout_multiget`` (benchmark code around the
program, as ``bench/serve.py``'s wrappers are) and keeps, for every
multiget that went to two shards or more, the slowest and the fastest
shard call.
"""

from __future__ import annotations

import time


class FanoutClock:
    """Times the shard calls of every fanned-out multiget of ``routers``
    from now on."""

    def __init__(self, routers):
        self.slowest_s: list[float] = []
        self.fastest_s: list[float] = []
        # shard call seconds by the id of its id list, which the fan-out
        # hands unchanged to the shard call; only fan-outs register theirs
        self._took: dict[int, float | None] = {}
        for router in routers:
            self._wrap(router)

    def _wrap(self, router) -> None:
        shard_call, fan_out = router._shard_multiget, router._fanout_multiget
        took = self._took

        def timed_shard_call(k, local_ids, read_preference=None):
            t0 = time.perf_counter()
            try:
                return shard_call(k, local_ids, read_preference)
            finally:
                if id(local_ids) in took:
                    took[id(local_ids)] = time.perf_counter() - t0

        def timed_fan_out(jobs, read_preference=None):
            if len(jobs) < 2:
                return fan_out(jobs, read_preference)
            keys = [id(local_ids) for _, local_ids in jobs]
            for key in keys:
                took[key] = None
            try:
                return fan_out(jobs, read_preference)
            finally:
                got = [took.pop(key) for key in keys]
                if None not in got:
                    self.slowest_s.append(max(got))
                    self.fastest_s.append(min(got))

        router._shard_multiget = timed_shard_call
        router._fanout_multiget = timed_fan_out

    def summary(self) -> dict:
        """``slowest_s`` and ``fastest_s``, one pair per fanned-out
        multiget."""
        return {"slowest_s": list(self.slowest_s),
                "fastest_s": list(self.fastest_s)}
