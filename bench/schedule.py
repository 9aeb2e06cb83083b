"""Request schedules drawn from a traffic file and ``--seed``.

A traffic file (``bench/traffic/<traffic>.json``) gives the parameters and
this module is the one generator that reads them:

- ``loop``: ``closed`` (``clients`` callers, each waiting for its answer)
  or ``open`` (Poisson arrivals at ``rate`` per second, sent when due);
- ``op``: ``multiget`` of ``fanout`` ids, or ``get`` of one id;
- ``ids``: ``dist`` ``uniform`` or ``zipf`` (exponent ``zipf_s``, ranks
  scattered over the id range by the multiplicative hash ``scatter``);
  ``unique`` (uniform only) and ``sorted`` within a request;
- ``warm``: ``requests`` multigets of ``fanout`` ids from the same
  distribution, sent before the window (caches filled as in steady state);
- ``requests_per_client``: closed-loop requests drawn up front, per client.

Every stream is its own ``numpy`` generator seeded by ``[seed, stream]``,
so a closed-loop client sends the same requests whatever the timing, and
two runs of one seed send the same traffic.
"""

from __future__ import annotations

import numpy as np

#: stream numbers under one seed (the corpus uses the bare seed)
WINDOW, WARM = 1, 2


def stream(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *key])


class IdSampler:
    """Ids of one request, drawn from a traffic file's ``ids`` block over a
    store of ``n`` strings."""

    def __init__(self, ids: dict, n: int):
        self.n = int(n)
        self.dist = ids.get("dist", "uniform")
        self.unique = bool(ids.get("unique", False))
        self.sorted = bool(ids.get("sorted", False))
        if self.dist == "zipf":
            if self.unique:
                raise ValueError("unique ids are drawn uniformly only")
            pmf = np.arange(1, self.n + 1, dtype=np.float64) ** -float(
                ids["zipf_s"])
            self.cdf = np.cumsum(pmf)
            self.cdf /= self.cdf[-1]
            self.scatter = int(ids.get("scatter", 1))
        elif self.dist != "uniform":
            raise ValueError(f"unknown id distribution {self.dist!r}")

    def draw(self, rng: np.random.Generator, k: int) -> np.ndarray:
        """``k`` ids (int64) of one request."""
        if self.dist == "zipf":
            ranks = np.searchsorted(self.cdf, rng.random(k), side="left")
            ids = (ranks.astype(np.int64) * self.scatter) % self.n
        elif self.unique:
            ids = rng.choice(self.n, size=k, replace=False).astype(np.int64)
        else:
            ids = rng.integers(0, self.n, size=k, dtype=np.int64)
        return np.sort(ids) if self.sorted else ids


def requests(sampler: IdSampler, rng: np.random.Generator, count: int,
             fanout: int) -> list[np.ndarray]:
    """``count`` requests of ``fanout`` ids each."""
    return [sampler.draw(rng, fanout) for _ in range(count)]


def arrivals(rate: float, rng: np.random.Generator, seconds: float
             ) -> np.ndarray:
    """Poisson arrival offsets (seconds) at ``rate`` per second covering
    ``seconds``."""
    count = int(rate * seconds * 1.2) + 64
    at = np.cumsum(rng.exponential(1.0 / rate, size=count))
    while at[-1] < seconds:
        at = np.concatenate((at, at[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=count))))
    return at[at < seconds]
