#!/usr/bin/env python3
"""Chip smoke test: the compressed string store served from a TPU.

    python chip_smoke.py              # one chip (what CI on a TPU host runs)
    python chip_smoke.py --chips 4    # four chips: a 4-shard shard:// store

One chip. The parent generates the ``urls`` corpus from ``--seed`` (256 MiB
of raw strings, about four million), trains OnPair16 with the store's
defaults and saves a one-shard store, all on the host with
``backend="numpy"``: it never initialises JAX, so the chip stays free. One
``python -m repro.net`` child owns the chip and serves the shard; its READY
line must name backend ``jax`` on platform ``tpu``. Through
``connect("tcp://...")`` the parent then sends a few hundred zipf multigets
of 64-256 ids over the whole id range (mostly cache misses, so they reach
the decode kernel), single gets, a scan and locate hits and misses, and
compares every answer with the source strings. The server's kernel
counters must show compiled Pallas decode batches and no jnp-reference
ones.

Four chips (``--chips 4``). One process builds a ``shard://`` store of four
shards, checks that shard *k* keeps its device tables on
``jax.devices()[k]``, and checks multigets whose ids span every shard.

Progress goes to stdout line by line; the last line is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed. Where no TPU is present, or any
phase fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import select
import shutil
import signal
import subprocess
import sys
import time
from contextlib import contextmanager

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, SRC)

from repro.data.synth import load_dataset  # noqa: E402
from repro.distributed.shard_store import save_sharded  # noqa: E402
from repro.store import CompressedStringStore  # noqa: E402

#: the platform every device phase must run on
PLATFORM = "tpu"
#: raw corpus MiB: a real urls store on one chip; the four-chip phase only
#: checks shard placement, so it builds a smaller one
ONE_CHIP_MIB = 256
FOUR_CHIP_MIB = 32
#: multigets per phase
N_MULTIGETS = 300
#: YCSB's zipfian constant
ZIPF_S = 0.99
READY_TIMEOUT_S = 600
_READY_RE = re.compile(r"SHARD_SERVER_READY port=(?P<port>\d+) .*"
                       r"backend=(?P<backend>\w+)"
                       r"(?: platform=(?P<platform>\w+))?")


class SmokeFailure(Exception):
    """A phase's answer or precondition was wrong."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def child_env() -> dict:
    env = {k: v for k, v in os.environ.items() if k != "REPRO_NO_JAX"}
    env["PYTHONPATH"] = SRC + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    return env


def probe_devices() -> dict:
    """The host's JAX devices, asked in a child that exits before the
    server starts (a parent holding the chip would starve its children)."""
    code = ("import json, jax\n"
            "d = jax.devices()\n"
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))\n")
    out = subprocess.run([sys.executable, "-c", code], env=child_env(),
                         capture_output=True, text=True, timeout=300)
    check(out.returncode == 0, f"device probe failed:\n{out.stderr[-2000:]}")
    device = json.loads(out.stdout.strip().splitlines()[-1])
    check(device["platform"] == PLATFORM,
          f"no {PLATFORM} present: JAX reports {device}")
    return device


def zipf_cdf(n: int) -> np.ndarray:
    """Truncated zipf CDF over ranks 1..n."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -ZIPF_S)
    return cdf / cdf[-1]


def zipf_ids(rng: np.random.Generator, cdf: np.ndarray,
             count: int) -> np.ndarray:
    """Zipf-popular ids: ranks drawn from ``cdf``, scattered over the whole
    id range by a multiplicative hash."""
    drawn = np.searchsorted(cdf, rng.random(count), side="left")
    return (drawn.astype(np.int64) * 2654435761) % cdf.size


def build_corpus(work: str, mib: int, seed: int, n_shards: int):
    """Generate, train, compress and save on the host; no device."""
    t0 = time.perf_counter()
    strings = load_dataset("urls", mib << 20, seed=seed)
    t1 = time.perf_counter()
    store = CompressedStringStore.build(strings, seed=seed, backend="numpy")
    save_sharded(store, work, n_shards)
    t2 = time.perf_counter()
    raw = sum(map(len, strings))
    log(f"corpus: urls seed={seed} {len(strings)} strings, {raw} raw bytes "
        f"({raw / 2**20:.1f} MiB), {store.corpus.compressed_bytes} "
        f"compressed bytes, ratio {raw / store.corpus.compressed_bytes:.3f}, "
        f"{store.dictionary.num_entries} dictionary entries, bucket caps "
        f"{[int(c) for c in store.bucket_caps]}")
    log(f"setup: generate {t1 - t0:.1f} s, train+compress+save "
        f"{t2 - t1:.1f} s")
    return strings


@contextmanager
def shard_server(shard_dir: str):
    """One ``python -m repro.net`` child serving ``shard_dir``; yields the
    parsed READY line. Interrupted (then killed) on exit."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.net", shard_dir],
        stdout=subprocess.PIPE, stderr=sys.stderr, text=True,
        env=child_env(), cwd=ROOT)
    try:
        t0 = time.perf_counter()
        m = line = None
        while m is None:
            left = READY_TIMEOUT_S - (time.perf_counter() - t0)
            ready = left > 0 and select.select([proc.stdout], [], [], left)[0]
            line = proc.stdout.readline() if ready else ""
            check(line != "", "shard server never became ready (exit code "
                  f"{proc.poll()})")
            m = _READY_RE.search(line)
        log(f"server: {line.strip()} after {time.perf_counter() - t0:.1f} s")
        yield m
    finally:
        proc.send_signal(signal.SIGINT)   # the server closes cleanly on ^C
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)


def kernel_batches(stats: dict) -> dict:
    """repro_kernel_decode_batches_total by path, from the stats RPC."""
    rows = (stats.get("metrics") or {}).get("metrics", [])
    counts = {"pallas": 0, "ref": 0}
    for row in rows:
        if row.get("name") == "repro_kernel_decode_batches_total":
            path = row["labels"]["path"]
            counts[path] = counts.get(path, 0) + int(row["value"])
    return counts


def check_reads(client, strings: list, rng: np.random.Generator,
                n_multigets: int) -> None:
    """Multigets, gets, a scan and locates, each against ``strings``."""
    n = len(strings)
    cdf = zipf_cdf(n)
    n_ids = 0
    t0 = time.perf_counter()
    for k in range(n_multigets):
        ids = zipf_ids(rng, cdf, int(rng.integers(64, 257))).tolist()
        got = client.multiget(ids)
        check(got == [strings[i] for i in ids], f"multiget {k} mismatched")
        n_ids += len(ids)
    log(f"multiget: {n_multigets} requests, {n_ids} ids, all byte-identical "
        f"({time.perf_counter() - t0:.1f} s on the host clock)")
    ids = rng.integers(0, n, 50).tolist()
    for i in ids:
        check(client.get(i) == strings[i], f"get({i}) mismatched")
    log(f"get: {len(ids)} ids, all byte-identical")
    lo = int(rng.integers(0, n - 200))
    check(client.scan(lo, lo + 200) == strings[lo:lo + 200],
          f"scan [{lo}, {lo + 200}) mismatched")
    log(f"scan: [{lo}, {lo + 200}) byte-identical")
    hits = [strings[int(i)] for i in rng.integers(0, n, 5)]
    misses = [b"chip-smoke-absent-" + bytes(rng.integers(97, 123, 24)
                                            .astype(np.uint8))
              for _ in range(5)]
    t0 = time.perf_counter()
    for q in hits:
        check(client.locate(q) == strings.index(q), f"locate({q!r}) wrong")
    for q in misses:
        check(q not in strings and client.locate(q) is None,
              f"locate({q!r}) found an absent string")
    log(f"locate: {len(hits)} hits and {len(misses)} misses correct "
        f"({time.perf_counter() - t0:.1f} s, segment indexes built on first "
        "use)")


def one_chip(args) -> dict:
    from repro.client import connect

    device = probe_devices()
    log(f"device: {device}")
    work = os.path.join(args.work, "one-chip")
    strings = build_corpus(work, ONE_CHIP_MIB, args.seed, n_shards=1)
    rng = np.random.default_rng(args.seed)
    with shard_server(os.path.join(work, "shard-0000")) as ready:
        check(ready["backend"] == "jax",
              f"server resolved backend {ready['backend']!r}, not jax")
        check(ready["platform"] == PLATFORM,
              f"server decodes on {ready['platform']!r}, not {PLATFORM}")
        client = connect(f"tcp://127.0.0.1:{ready['port']}")
        try:
            check_reads(client, strings, rng, N_MULTIGETS)
            stats = client.backend.clients[0].stats(metrics=True)
        finally:
            client.close()
    snap = stats["store"]
    counts = kernel_batches(stats)
    log(f"kernel: decode batches {counts}, jit shapes {snap['jit_shapes']}, "
        f"first batch per shape (compile included) {snap['first_batch_s']} s, "
        f"device {snap.get('device')}")
    log(f"store: {snap['lookups']} lookups, {snap['decoded_strings']} "
        f"decoded, cache {snap['cache']}")
    check(counts["pallas"] > 0, "no Pallas decode batch ran")
    check(counts["ref"] == 0, "the jnp reference decoded on the serving path")
    return device


def four_chips(args) -> dict:
    import jax

    from repro.client import connect

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device}")
    check(device["platform"] == PLATFORM, f"no {PLATFORM} present: {device}")
    check(len(devices) >= 4, f"--chips 4 needs four devices: {device}")
    work = os.path.join(args.work, "four-chips")
    strings = build_corpus(work, FOUR_CHIP_MIB, args.seed, n_shards=4)
    client = connect(f"shard://{work}")
    try:
        shards = client.backend.stats_snapshot()["shards"]
        placed = [s.get("device") for s in shards]
        for k, dev in enumerate(placed):
            log(f"shard {k}: {client.backend.bounds[k]} on {dev}")
        check(all(d and d["platform"] == PLATFORM for d in placed),
              f"a shard does not decode on {PLATFORM}: {placed}")
        check(len({d["id"] for d in placed}) == 4,
              f"shards share a device: {placed}")
        rng = np.random.default_rng(args.seed)
        n = len(strings)
        n_ids = 0
        for k in range(N_MULTIGETS):
            ids = rng.integers(0, n, int(rng.integers(64, 257))).tolist()
            check({client.backend.route(i)[0] for i in ids} == {0, 1, 2, 3},
                  f"multiget {k} does not span every shard")
            check(client.multiget(ids) == [strings[i] for i in ids],
                  f"multiget {k} mismatched")
            n_ids += len(ids)
        log(f"multiget: {N_MULTIGETS} requests, {n_ids} ids over four "
            "shards, all byte-identical")
        batches = [s["batches"] for s in client.backend.stats_snapshot()
                   ["shards"]]
        log(f"decode batches per shard: {batches}")
        check(all(b > 0 for b in batches), "a shard decoded nothing")
    finally:
        client.close()
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated corpus and the request ids")
    args = ap.parse_args(argv)
    args.work = os.path.join(ROOT, ".chip_smoke")  # the built store
    from repro.kernels.cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    try:
        device = one_chip(args) if args.chips == 1 else four_chips(args)
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(args.work, ignore_errors=True)
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
