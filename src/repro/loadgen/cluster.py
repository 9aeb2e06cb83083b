"""Spawn-or-attach: a throwaway multi-process cluster for one load run.

``python -m repro.loadgen --spawn <dir>`` needs real sockets and real
process isolation — an in-thread server shares the GIL with the driver
and understates every latency. :class:`LocalCluster` launches one
``python -m repro.net`` process per shard (plus optional read-only
replicas), waits on each ``SHARD_SERVER_READY`` announce line, records
replica addresses into the cluster manifest (so the client's replica
autodiscovery wires read load-balancing on connect), and tears everything
down on exit. Children run ``REPRO_NO_JAX=1``: a chip belongs to one
process, so several shard processes on one host serve from numpy, and
skipping the jax import keeps spawn latency off the measurement. The
spawner itself never initialises JAX (``build_demo_corpus`` builds with
``backend="numpy"``).
"""

from __future__ import annotations

import os
import re
import subprocess
import sys

from repro.distributed.shard_store import record_replicas

_READY_RE = re.compile(
    r"SHARD_SERVER_READY port=(?P<port>\d+)"
    r".*?(?:metrics_port=(?P<mport>\d+))?\s+dir=")
_SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _child_env() -> dict:
    env = {**os.environ, "REPRO_NO_JAX": "1"}
    env["PYTHONPATH"] = _SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


class LocalCluster:
    """Shard server processes over one sharded directory; context-managed."""

    def __init__(self, dir_path: str):
        self.dir = dir_path
        self.procs: list[subprocess.Popen] = []
        self.addresses: list[tuple[str, int]] = []      # primaries, shard order
        self.metrics_addrs: list[tuple[str, int]] = []  # primaries, shard order
        self.replica_addresses: dict[int, list[tuple[str, int]]] = {}

    # ------------------------------------------------------------------ spawn
    def _launch(self, shard: int, read_only: bool,
                metrics: bool) -> tuple[tuple[str, int], tuple[str, int] | None]:
        argv = [sys.executable, "-m", "repro.net",
                os.path.join(self.dir, f"shard-{shard:04d}")]
        if read_only:
            argv.append("--read-only")
        if metrics:
            argv += ["--metrics-port", "0"]
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True,
                                env=_child_env())
        line = proc.stdout.readline()
        m = _READY_RE.search(line or "")
        if not m:
            proc.terminate()
            self.close()
            raise RuntimeError(
                f"shard server {shard} (read_only={read_only}) never became "
                f"ready: {line!r}")
        self.procs.append(proc)
        addr = ("127.0.0.1", int(m.group("port")))
        maddr = (("127.0.0.1", int(m.group("mport")))
                 if m.group("mport") else None)
        return addr, maddr

    @classmethod
    def spawn(cls, dir_path: str, n_shards: int | None = None,
              replicas: int = 0, metrics: bool = True) -> "LocalCluster":
        """Launch primaries for every ``shard-NNNN`` under ``dir_path``
        (``n_shards`` limits/checks the count), plus ``replicas`` read-only
        servers per shard, recorded in the manifest for autodiscovery."""
        found = sorted(d for d in os.listdir(dir_path)
                       if re.fullmatch(r"shard-\d{4}", d))
        if not found:
            raise FileNotFoundError(f"no shard-NNNN dirs under {dir_path}")
        if n_shards is not None and len(found) != n_shards:
            raise ValueError(
                f"{dir_path} holds {len(found)} shards, expected {n_shards}")
        cluster = cls(dir_path)
        try:
            for k in range(len(found)):
                addr, maddr = cluster._launch(k, read_only=False,
                                              metrics=metrics)
                cluster.addresses.append(addr)
                if maddr:
                    cluster.metrics_addrs.append(maddr)
            if replicas:
                for k in range(len(found)):
                    addrs = [cluster._launch(k, read_only=True,
                                             metrics=False)[0]
                             for _ in range(replicas)]
                    cluster.replica_addresses[k] = addrs
                record_replicas(dir_path, cluster.replica_addresses)
        except BaseException:
            cluster.close()
            raise
        return cluster

    # ------------------------------------------------------------------ attach
    @property
    def url(self) -> str:
        hosts = ",".join(f"{h}:{p}" for h, p in self.addresses)
        return f"tcp://{hosts}"

    def connect_kw(self) -> dict:
        """Keyword args for ``repro.client.connect`` against this cluster
        (manifest path enables replica autodiscovery + save/compact)."""
        return {"dir_path": self.dir}

    # ---------------------------------------------------------------- teardown
    def close(self) -> None:
        for p in self.procs:
            p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5)
        self.procs.clear()

    def __enter__(self) -> "LocalCluster":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def build_demo_corpus(dir_path: str, n_shards: int = 2,
                      target_mib: int = 8, dataset: str = "urls",
                      seed: int = 0) -> int:
    """Train + shard a synthetic corpus under ``dir_path`` (idempotent:
    an existing manifest short-circuits). Returns ``n_strings``."""
    from repro.data.synth import load_dataset
    from repro.distributed.shard_store import MANIFEST, save_sharded
    from repro.store import CompressedStringStore

    manifest = os.path.join(dir_path, MANIFEST)
    if os.path.exists(manifest):
        import json
        with open(manifest, encoding="utf-8") as fh:
            bounds = json.load(fh)["bounds"]
        return bounds[-1][1]
    strings = load_dataset(dataset, target_mib << 20, seed=seed)
    # training and compressing need no device: stay off JAX, so a child
    # started from this process can own the chip
    store = CompressedStringStore.build(strings, seed=seed, backend="numpy")
    os.makedirs(dir_path, exist_ok=True)
    save_sharded(store, dir_path, n_shards)
    return len(strings)
