"""Tests for the repro.net multi-process serving tier.

Protocol framing (round-trips, truncation, oversize refusal), server/router
loopback equivalence against the in-process ShardedStringStore on the same
directories, request-order preservation under concurrent fan-out, retry
across a shard process kill/restart, replica-backed compaction hand-off,
and the StoreService no-busy-wait contract.

Everything here is stdlib + numpy (the point of the RPC tier: serving hosts
without jax stay covered); spawned child processes run with REPRO_NO_JAX=1
so startup stays fast on jax-equipped containers too.
"""

import os
import re
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.data.synth import load_dataset
from repro.distributed import ShardedStringStore, save_sharded
from repro.net import (
    DistributedStringStore,
    FrameTooLargeError,
    ProtocolError,
    RemoteError,
    RemoteShardClient,
    ShardServer,
    TruncatedFrameError,
)
from repro.net import protocol as P
from repro.store import CompressedStringStore, StoreService

SAMPLE = 1 << 18
# .../src/repro/net/protocol.py -> .../src (repro may be a namespace package)
SRC_DIR = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(P.__file__))))
CHILD_ENV = {**os.environ, "PYTHONPATH": SRC_DIR, "REPRO_NO_JAX": "1"}


@pytest.fixture(scope="module")
def titles():
    strings = load_dataset("book_titles", SAMPLE)
    strings[3] = b""
    strings[7] = b"\x00\xff" * 9
    return strings


@pytest.fixture(scope="module")
def sharded_dir(titles, tmp_path_factory):
    store = CompressedStringStore.build(
        titles, sample_bytes=SAMPLE, strings_per_segment=256
    )
    d = str(tmp_path_factory.mktemp("net") / "shards")
    save_sharded(store, d, 3)
    return d


@pytest.fixture()
def cluster(sharded_dir):
    servers = [
        ShardServer.from_dir(os.path.join(sharded_dir, f"shard-{k:04d}")).start()
        for k in range(3)
    ]
    dist = DistributedStringStore.connect(
        [s.address for s in servers], dir_path=sharded_dir
    )
    yield dist, servers
    dist.close()
    for s in servers:
        s.close()


def _spawn_server(args, via_launcher=False):
    """Start a shard server child process; returns (proc, (host, port))."""
    mod = ["-m", "repro.launch.serve", "--shard-server"] if via_launcher else [
        "-m",
        "repro.net",
    ]
    proc = subprocess.Popen(
        [sys.executable, *mod, *args],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
        env=CHILD_ENV,
    )
    line = proc.stdout.readline()
    m = re.search(r"SHARD_SERVER_READY port=(\d+)", line)
    if not m:
        proc.terminate()
        raise AssertionError(
            f"server never became ready: {line!r}\n{proc.stderr.read()}"
        )
    return proc, ("127.0.0.1", int(m.group(1)))


# ------------------------------------------------------------------- protocol
def test_frame_roundtrip_all_ops():
    for kind in list(P.OP_NAMES) + [P.ST_OK, P.ST_ERR]:
        payload = os.urandom(kind)  # varied sizes, including empty
        buf = P.encode_frame(kind, payload)
        got_kind, got_payload, used = P.decode_frame(buf + b"trailing")
        assert (got_kind, got_payload, used) == (kind, payload, len(buf))


def test_frame_rejects_bad_magic_and_version():
    frame = bytearray(P.encode_frame(P.OP_PING, b"x"))
    frame[0] = ord("X")
    with pytest.raises(ProtocolError):
        P.decode_frame(bytes(frame))
    frame = bytearray(P.encode_frame(P.OP_PING, b"x"))
    frame[2] = 99  # version byte
    with pytest.raises(ProtocolError):
        P.decode_frame(bytes(frame))


def test_oversized_frame_refused_from_header_alone():
    frame = P.encode_frame(P.OP_EXTEND, b"a" * 1024)
    with pytest.raises(FrameTooLargeError):
        P.decode_frame(frame, max_frame=512)
    # the declared length alone triggers refusal — payload bytes not needed
    with pytest.raises(FrameTooLargeError):
        P.decode_header(frame[: P.HEADER_BYTES], max_frame=512)


def test_truncated_frame_detected_at_every_cut():
    frame = P.encode_frame(P.OP_MULTIGET, P.pack_ids([1, 2, 3]))
    for cut in range(len(frame)):
        with pytest.raises(TruncatedFrameError):
            P.decode_frame(frame[:cut])


def test_truncated_frame_over_socket():
    a, b = socket.socketpair()
    frame = P.encode_frame(P.OP_PING, b"payload")
    a.sendall(frame[: len(frame) - 3])
    a.close()
    with pytest.raises(TruncatedFrameError):
        P.recv_frame(b)
    b.close()
    # clean EOF at a frame boundary is None, not an error
    a, b = socket.socketpair()
    a.sendall(frame)
    a.close()
    assert P.recv_frame(b) == (P.OP_PING, b"payload")
    assert P.recv_frame(b) is None
    b.close()


def test_payload_helpers_roundtrip():
    ids = [0, 1, 2**40, 7]
    assert P.unpack_ids(P.pack_ids(ids)) == ids
    assert P.unpack_ids(b"") == []
    items = [b"", b"a", b"\x00\xff" * 100, b"", b"tail"]
    assert P.unpack_bytes_list(P.pack_bytes_list(items)) == items
    assert P.unpack_bytes_list(P.pack_bytes_list([])) == []
    with pytest.raises(ProtocolError):
        P.unpack_ids(b"odd")
    with pytest.raises(ProtocolError):
        P.unpack_bytes_list(b"\x01")


def test_remote_error_mapping():
    with pytest.raises(IndexError, match="out of range"):
        P.raise_remote(P.pack_error(IndexError("id 9 out of range")))
    with pytest.raises(RemoteError, match="OSError"):
        P.raise_remote(P.pack_error(OSError("disk on fire")))


# ------------------------------------------------- service: no-busy-wait fix
def test_service_idle_without_wakeups(titles):
    store = CompressedStringStore.build(titles[:64], sample_bytes=SAMPLE)
    with StoreService(store) as svc:
        time.sleep(0.3)  # several _POLL_S periods of the old polling drain
        assert svc.wakeups == 0, "idle service must not wake its worker"
        assert svc.batches == 0
        assert svc.get(5) == titles[5]
        assert svc.wakeups >= 1
        wakes = svc.wakeups
        time.sleep(0.2)
        assert svc.wakeups == wakes  # back to fully idle after traffic


def test_service_bulk_hooks(titles):
    store = CompressedStringStore.build(titles[:128], sample_bytes=SAMPLE)
    with StoreService(store) as svc:
        fut = svc.submit_multiget([5, 3, 5, 127])
        assert fut.result(30) == [titles[5], titles[3], titles[5], titles[127]]
        with pytest.raises(IndexError):
            svc.submit_multiget([0, 128]).result(30)
        with pytest.raises(TypeError):
            svc.submit_extend([b"x"]).result(30)  # read-only store
        # only the served batch counts: failed validations never enqueue
        assert svc.stats()["requests"] == 4


def test_service_close_during_inflight_batch_does_not_hang(titles):
    store = CompressedStringStore.build(titles[:64], sample_bytes=SAMPLE)
    svc = StoreService(store, max_wait_s=0.2)  # wide window to land close() in
    orig = store.multiget

    def slow_multiget(ids):
        time.sleep(0.3)
        return orig(ids)

    store.multiget = slow_multiget
    fut = svc.submit(5)
    time.sleep(0.05)  # worker is now inside the batch window / decode
    t0 = time.time()
    svc.close()
    assert time.time() - t0 < 3.0, "close() stalled on a lost sentinel"
    assert not svc._worker.is_alive()
    assert fut.result(1) == titles[5]


# --------------------------------------------------------- loopback equality
def test_router_matches_local_sharded_store(cluster, sharded_dir, titles):
    dist, _ = cluster
    local = ShardedStringStore.open(sharded_dir)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, len(titles), 800).tolist()
    assert dist.multiget(ids) == local.multiget(ids)
    assert dist.get(3) == titles[3] == local.get(3)
    lo, hi = len(titles) // 3 - 50, len(titles) // 3 + 50  # straddles shards
    assert dist.scan(lo, hi) == local.scan(lo, hi) == titles[lo:hi]
    assert dist.n_strings == local.n_strings == len(titles)
    snap = dist.stats_snapshot()
    assert snap["n_shards"] == 3
    assert snap["bounds"] == [list(b) for b in local.bounds]
    assert all(s["service"]["requests"] >= 0 for s in snap["shards"])
    with pytest.raises(IndexError):
        dist.get(len(titles))
    with pytest.raises(IndexError):
        dist.multiget([0, len(titles)])


def test_order_preserved_under_concurrent_fanout(cluster, titles):
    dist, _ = cluster
    errs = []

    def client(seed):
        try:
            rng = np.random.default_rng(seed)
            for _ in range(10):
                ids = rng.integers(0, len(titles), 200).tolist()
                assert dist.multiget(ids) == [titles[i] for i in ids]
        except Exception as exc:  # pragma: no cover - failure path
            errs.append(exc)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[0]


def test_router_appends_route_to_tail_shard(cluster, titles):
    dist, servers = cluster
    n0 = dist.n_strings
    new = [b"net-append-%d" % i for i in range(300)]
    ids = dist.extend(new[:200])
    ids += [dist.append(s) for s in new[200:210]]
    futs = [dist.extend(new[210 + 3 * k : 213 + 3 * k]) for k in range(30)]
    ids += [i for chunk in futs for i in chunk]
    assert ids == list(range(n0, n0 + 300))
    assert dist.multiget(ids) == new
    assert dist.scan(n0 - 5, n0 + 300) == dist.multiget(
        range(n0 - 5, n0 + 300)
    )
    # every append landed on the tail shard's server, none elsewhere
    assert servers[-1].store.n_strings - (dist.bounds[-1][1] - dist.bounds[-1][0]) == 0
    assert servers[0].store.n_strings == dist.bounds[0][1]


def test_oversized_request_surfaces_instead_of_retrying(sharded_dir, titles):
    with ShardServer.from_dir(
        os.path.join(sharded_dir, "shard-0000"), max_frame=4096
    ).start() as server:
        client = RemoteShardClient(server.address)
        assert client.get(0) == titles[0]
        with pytest.raises(FrameTooLargeError, match="max_frame"):
            client.extend([b"x" * 16384])
        assert client.reconnects == 0  # refused once, not resent 17 times
        assert client.get(1) == titles[1]  # client reconnects cleanly after
        client.close()


def test_distributed_scan_chunks_below_max_frame(cluster, sharded_dir, titles):
    dist, _ = cluster
    dist.scan_chunk = 64  # force many small RPCs across shard boundaries
    lo, hi = 100, 1200
    assert dist.scan(lo, hi) == titles[lo:hi]


def test_server_refuses_writes_when_read_only(sharded_dir):
    with ShardServer.from_dir(
        os.path.join(sharded_dir, "shard-0000"), read_only=True
    ).start() as server:
        client = RemoteShardClient(server.address)
        assert client.get(0) == client.multiget([0])[0]
        with pytest.raises(TypeError):
            client.append(b"nope")
        with pytest.raises(TypeError):
            client.compact()
        assert client.stats()["writable"] is False
        client.close()


# ------------------------------------------------------- process lifecycles
def test_router_retries_across_server_restart(titles, tmp_path):
    store = CompressedStringStore.build(
        titles[:2000], sample_bytes=SAMPLE, strings_per_segment=256
    )
    d = str(tmp_path / "shards")
    save_sharded(store, d, 2)
    shard_dirs = [os.path.join(d, f"shard-{k:04d}") for k in range(2)]
    procs, addrs = [], []
    for k, sd in enumerate(shard_dirs):
        # shard 0 via the serve.py launcher (covers the --shard-server role),
        # shard 1 via python -m repro.net
        proc, addr = _spawn_server([sd], via_launcher=(k == 0))
        procs.append(proc)
        addrs.append(addr)
    dist = DistributedStringStore.connect(addrs, dir_path=d)
    try:
        assert dist.get(1) == titles[1]
        mid = dist.bounds[1][0] + 5
        assert dist.get(mid) == titles[mid]

        procs[1].terminate()
        procs[1].wait()
        with pytest.raises((ConnectionError, OSError)):
            # fast-failing client so the dead window is observed
            RemoteShardClient(addrs[1], reconnect_attempts=1).multiget([0])

        procs[1], _ = _spawn_server(
            [shard_dirs[1], "--port", str(addrs[1][1])]
        )
        assert dist.get(mid) == titles[mid]  # reconnects transparently
        assert dist.clients[1].reconnects >= 1
    finally:
        dist.close()
        for proc in procs:
            proc.terminate()


def test_replica_failover_during_live_compact(titles, tmp_path):
    store = CompressedStringStore.build(
        titles[:1500], sample_bytes=SAMPLE, strings_per_segment=256
    )
    d = str(tmp_path / "shards")
    save_sharded(store, d, 2)
    tail_dir = os.path.join(d, "shard-0001")
    servers = [
        ShardServer.from_dir(os.path.join(d, f"shard-{k:04d}")).start()
        for k in range(2)
    ]
    dist = DistributedStringStore.connect(
        [s.address for s in servers], dir_path=d
    )
    replica = None
    try:
        pre_ids = dist.extend([b"pre-compact-%d" % i for i in range(20)])
        dist.save()  # replica opens the saved (current) generation

        replica = ShardServer.from_dir(tail_dir, read_only=True).start()
        with pytest.raises(ValueError):  # a writable "replica" is refused
            dist.register_replica(1, servers[1].address)
        dist.register_replica(1, replica.address)
        # the replica's first reads compile its decode shapes (jax backend);
        # take that cost before the hand-off window timed below
        dist.multiget(pre_ids, read_preference="replica")

        # stretch the compaction window so the hand-off is observable
        primary_store = servers[1].store
        orig_compact = primary_store.compact

        def slow_compact(**kw):
            time.sleep(0.6)
            return orig_compact(**kw)

        primary_store.compact = slow_compact
        reports = {}

        def run_compact():
            reports["compact"] = dist.compact(1)

        compacter = threading.Thread(target=run_compact)
        compacter.start()
        deadline = time.time() + 5
        while not dist._draining.get(1) and time.time() < deadline:
            time.sleep(0.01)
        assert dist._draining.get(1), "compact never entered hand-off"

        # reads drain to the replica and never block on the rewrite
        t0 = time.time()
        assert dist.get(pre_ids[3]) == b"pre-compact-3"
        assert dist.multiget(pre_ids) == [b"pre-compact-%d" % i for i in range(20)]
        assert time.time() - t0 < 0.5
        replica_client, replica_n = dist._replicas[1][0]
        assert replica_client.n_strings >= pre_ids[-1] - dist.bounds[1][0]
        assert replica_n == replica_client.n_strings

        # appends park in the retry queue and are acknowledged post-swap
        mid_id = dist.append(b"appended-during-compact")
        compacter.join(timeout=30)
        assert reports["compact"][0]["n_strings"] > 0
        assert mid_id == pre_ids[-1] + 1
        assert dist.get(mid_id) == b"appended-during-compact"

        # durable: persisted and visible to a fresh in-process open
        dist.save()
        local = ShardedStringStore.open(d)
        assert local.get(mid_id) == b"appended-during-compact"
        assert local.get(pre_ids[0]) == b"pre-compact-0"
    finally:
        dist.close()
        for s in servers:
            s.close()
        if replica is not None:
            replica.close()
