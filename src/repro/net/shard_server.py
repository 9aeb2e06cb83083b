"""ShardServer — one process serving one shard directory over TCP.

The process form of the serving story: a host owning ``<dir>/shard-000k``
opens it (shared dictionary artifact + its corpus slice, writable by
default) and answers the :mod:`repro.net.protocol` ops. Every connection is
a thread, but ALL reads funnel through one shared
:class:`~repro.store.service.StoreService` — concurrent connections'
``get``/``multiget`` requests coalesce into single batched store decodes,
and their ``append``/``extend`` requests fold into single Encoder passes,
so the micro-batching that made the in-process service fast survives the
move to sockets unchanged.

Run one per shard::

    python -m repro.net.shard_server /data/corpus/shard-0002 --port 9102
    python -m repro.launch.serve --shard-server /data/corpus/shard-0002

With ``--port 0`` the kernel assigns a free port and the server prints
``SHARD_SERVER_READY port=<p> ...`` on stdout — spawners (the example, the
rpc benchmark, tests) parse that line instead of racing for free ports. The
line names the decode backend the store resolved (``backend=jax`` or
``backend=numpy``) and, for jax, the device it serves from
(``platform=tpu kind="TPU v5 lite"``). On the jax backend this process owns
the chip: nothing that spawns it may have initialised JAX itself.
``--read-only`` serves a replica: same directory, current versioned
generation, appends and compaction refused — the hand-off target a router
drains reads to while the primary rewrites itself.

Set ``REPRO_NO_JAX=1`` in the environment to skip the jax import and serve
on the numpy decode path (fast startup; what a CPU-only serving host runs).
Otherwise ``main()`` turns on JAX's persistent compilation cache
(:func:`repro.kernels.cache.use_compile_cache`).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import socketserver
import threading

from repro.kernels.cache import use_compile_cache
from repro.net import protocol as P
from repro.obs import REGISTRY, TRACER, Counter, start_metrics_server
from repro.store.mutable import MutableStringStore
from repro.store.service import StoreService
from repro.store.store import CompressedStringStore

_SHARD_DIR_RE = re.compile(r"^shard-(\d{4})$")


def open_serving_store(
    path: str,
    read_only: bool = False,
    **overrides,
) -> CompressedStringStore:
    """Open ``path`` for serving.

    ``<parent>/shard-000k`` directories open through
    :func:`repro.distributed.shard_store.open_shard` (shared dictionary in
    the parent); anything else opens as a plain store directory. Writable
    unless ``read_only`` — a read-only open of a versioned shard serves its
    current generation, which is exactly what a compaction replica needs.
    """
    from repro.distributed.shard_store import MANIFEST, open_shard

    path = os.path.abspath(path)
    m = _SHARD_DIR_RE.match(os.path.basename(path))
    parent = os.path.dirname(path)
    if m and os.path.exists(os.path.join(parent, MANIFEST)):
        return open_shard(parent, int(m.group(1)), writable=not read_only, **overrides)
    if read_only:
        return CompressedStringStore.open(path, **overrides)
    return MutableStringStore.open(path, **overrides)


class _Handler(socketserver.BaseRequestHandler):
    """One connection: read frames until EOF, answer each synchronously.

    Concurrency comes from the threading server (one handler thread per
    connection) plus the shared StoreService batching across handlers —
    within a connection, requests pipeline strictly in order.
    """

    def handle(self) -> None:
        shard: "ShardServer" = self.server.shard_server  # type: ignore[attr-defined]
        sock = self.request
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        while True:
            try:
                frame = P.recv_frame_ex(sock, max_frame=shard.max_frame)
            except P.FrameTooLargeError as exc:
                # refuse loudly so the client sees WHY, then close: the
                # payload was never read, the stream cannot resynchronise
                try:
                    P.send_frame(sock, P.ST_ERR, P.pack_error(exc))
                except OSError:
                    pass
                return
            except P.ProtocolError:
                return  # torn/hostile frame: drop the connection
            except OSError:
                return
            if frame is None:
                return  # clean EOF
            kind, payload, trace = frame
            opname = P.OP_NAMES.get(kind, hex(kind))
            # a v2 frame's trace header joins this server's spans to the
            # client's trace; v1 frames dispatch untraced (span() no-ops)
            prev = TRACER.activate(trace) if trace is not None else None
            try:
                with TRACER.span(f"server.{opname}"):
                    resp = shard.dispatch(kind, payload)
                status = P.ST_OK
            except Exception as exc:
                resp = P.pack_error(exc)
                status = P.ST_ERR
            finally:
                if trace is not None:
                    TRACER.restore(prev)
            try:
                P.send_frame(sock, status, resp)
            except OSError:
                return


class _TCPServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class ShardServer:
    """TCP front-end over one store: the per-shard serving process."""

    def __init__(
        self,
        store: CompressedStringStore,
        host: str = "127.0.0.1",
        port: int = 0,
        max_batch: int = 256,
        max_wait_s: float = 0.0005,
        max_frame: int = P.DEFAULT_MAX_FRAME,
        target_p99_s: float | None = None,
    ):
        self.store = store
        self.max_frame = int(max_frame)
        self.service = StoreService(
            store,
            max_batch=max_batch,
            max_wait_s=max_wait_s,
            target_p99_s=target_p99_s,
        )
        # per-op request counters, exported via stats() and /metrics — the
        # observability a router-side test (or operator) uses to see WHICH
        # server answered. Counter.inc() is lock-protected: dispatch() runs
        # concurrently on per-connection handler threads, and a lost
        # increment would make replica-routing assertions flake.
        self._op_counters: dict[str, Counter] = {}
        self._op_lock = threading.Lock()  # guards counter *creation* only
        self._tcp = _TCPServer((host, port), _Handler)
        self._tcp.shard_server = self  # type: ignore[attr-defined]
        self._thread: threading.Thread | None = None

    # ------------------------------------------------------------------ server
    @classmethod
    def from_dir(
        cls,
        path: str,
        read_only: bool = False,
        host: str = "127.0.0.1",
        port: int = 0,
        **kw,
    ) -> "ShardServer":
        service_kw = {
            k: kw.pop(k)
            for k in ("max_batch", "max_wait_s", "max_frame", "target_p99_s")
            if k in kw
        }
        store = open_serving_store(path, read_only=read_only, **kw)
        return cls(store, host=host, port=port, **service_kw)

    @property
    def address(self) -> tuple[str, int]:
        host, port = self._tcp.server_address[:2]
        return str(host), int(port)

    @property
    def port(self) -> int:
        return self.address[1]

    def start(self) -> "ShardServer":
        """Serve in a background thread (tests / in-process topologies)."""
        self._thread = threading.Thread(
            target=self._tcp.serve_forever,
            kwargs={"poll_interval": 0.1},
            daemon=True,
            name=f"shard-server-{self.port}",
        )
        self._thread.start()
        return self

    def serve_forever(self) -> None:
        self._tcp.serve_forever(poll_interval=0.2)

    def close(self) -> None:
        self._tcp.shutdown()
        self._tcp.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        self.service.close()

    def __enter__(self) -> "ShardServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def op_counts(self) -> dict[str, int]:
        """Per-op request counts as a plain dict (`.get(op, 0)` friendly)."""
        with self._op_lock:
            return {name: c.value for name, c in self._op_counters.items()}

    def _count_op(self, opname: str) -> None:
        with self._op_lock:
            counter = self._op_counters.get(opname)
            if counter is None:
                counter = self._op_counters[opname] = REGISTRY.register(
                    Counter("repro_rpc_requests_total",
                            labels={"op": opname}))
        counter.inc()

    # ---------------------------------------------------------------- dispatch
    def dispatch(self, kind: int, payload: bytes) -> bytes:
        self._count_op(P.OP_NAMES.get(kind, hex(kind)))
        if kind == P.OP_PING:
            if payload == P.CAPS_PROBE:
                # capability negotiation: an old server would echo the probe
                # verbatim; answering with JSON is what marks us trace-aware
                return P.pack_json(P.SERVER_CAPS)
            return payload
        if kind == P.OP_GET:
            (i,) = P.unpack_ids(payload)
            return self.service.submit(i).result()
        if kind == P.OP_MULTIGET:
            ids = P.unpack_ids(payload)
            return P.pack_bytes_list(self.service.submit_multiget(ids).result())
        if kind == P.OP_SCAN:
            lo, hi = P.unpack_ids(payload)
            return P.pack_bytes_list(self.store.scan(lo, hi))
        if kind == P.OP_APPEND:
            return P.pack_ids(self.service.submit_extend([payload]).result())
        if kind == P.OP_EXTEND:
            strings = P.unpack_bytes_list(payload)
            return P.pack_ids(self.service.submit_extend(strings).result())
        if kind == P.OP_STATS:
            opts = P.unpack_json(payload) if payload else {}
            stats = self.stats()
            if opts.get("metrics"):
                # registry snapshot extension: mergeable histogram/counter
                # states for client-side cross-shard aggregation
                stats["metrics"] = REGISTRY.snapshot()
            return P.pack_json(stats)
        if kind == P.OP_LOCATE:
            strings = P.unpack_bytes_list(payload)
            found = self.store.locate_batch(strings)
            # None has no <i8 encoding: misses travel as -1
            return P.pack_ids([-1 if gid is None else gid for gid in found])
        if kind == P.OP_SCAN_PREFIX:
            prefix, limit, after = P.unpack_prefix_query(payload)
            return P.pack_prefix_hits(self.store.scan_prefix(prefix, limit, after))
        if kind == P.OP_TRACE_DUMP:
            n = (P.unpack_json(payload) or {}).get("n", 16) if payload else 16
            return P.pack_json(TRACER.trace_dump(n))
        if kind == P.OP_COMPACT:
            if not hasattr(self.store, "compact"):
                raise TypeError("store is read-only; compact() refused")
            kw = P.unpack_json(payload) if payload else {}
            # runs in this connection's handler thread: other connections
            # keep being served while the store rewrites itself
            return P.pack_json(self.store.compact(**kw))
        if kind == P.OP_TIER:
            from repro.store.tier import tier_op

            req = P.unpack_json(payload) if payload else {}
            return P.pack_json(
                tier_op(
                    self.store,
                    action=req.get("action", "stats"),
                    segment=req.get("segment"),
                    params=req.get("params"),
                )
            )
        if kind == P.OP_SAVE:
            target = getattr(self.store, "_dir", None)
            if not hasattr(self.store, "extend") or target is None:
                raise TypeError(
                    "store is read-only or has no backing directory; save refused"
                )
            self.store.save(target)
            return P.pack_json({"dir": target, "n_strings": self.store.n_strings})
        raise P.ProtocolError(f"unknown op 0x{kind:02x}")

    def stats(self) -> dict:
        ops = self.op_counts
        return {
            "n_strings": self.store.n_strings,
            "writable": hasattr(self.store, "extend"),
            "ops": ops,
            "store": self.store.stats_snapshot(),
            "service": self.service.stats(),
        }


def run(
    path: str,
    host: str = "127.0.0.1",
    port: int = 0,
    read_only: bool = False,
    max_batch: int = 256,
    max_wait_s: float = 0.0005,
    target_p99_s: float | None = None,
    announce: bool = True,
    metrics_port: int | None = None,
    encode_backend: str | None = None,
) -> None:
    """Open the store, print the readiness line, serve until interrupted.

    ``metrics_port`` (0 = kernel-assigned) additionally serves Prometheus
    text on ``http://<host>:<metrics_port>/metrics`` plus the slow-request
    trace dump on ``/traces``; the bound port rides the readiness line as
    ``metrics_port=``.
    """
    # only writable opens understand the knob: a read-only replica never
    # encodes, and CompressedStringStore.open has no such parameter
    write_kw = ({} if read_only or encode_backend is None
                else {"encode_backend": encode_backend})
    server = ShardServer.from_dir(
        path,
        read_only=read_only,
        host=host,
        port=port,
        max_batch=max_batch,
        max_wait_s=max_wait_s,
        target_p99_s=target_p99_s,
        **write_kw,
    )
    metrics = (start_metrics_server(port=metrics_port, host=host)
               if metrics_port is not None else None)
    if announce:
        snap = server.store.stats_snapshot()
        device = snap.get("device")
        # metrics_port= must stay right before dir=: spawners parse it there
        extra = ""
        if device:
            extra = f" platform={device['platform']} kind={json.dumps(device['kind'])}"
        if metrics is not None:
            extra += f" metrics_port={metrics.port}"
        print(
            f"SHARD_SERVER_READY port={server.port} "
            f"n_strings={server.store.n_strings} "
            f"writable={int(hasattr(server.store, 'extend'))} "
            f"backend={snap['backend']}"
            f"{extra} "
            f"dir={json.dumps(path)}",
            flush=True,
        )
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if metrics is not None:
            metrics.close()
        server.close()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", help="shard directory (<parent>/shard-000k) or store dir")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0, help="0 = kernel-assigned")
    ap.add_argument(
        "--read-only",
        action="store_true",
        help="serve as a replica: appends and compaction refused",
    )
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--max-wait-s", type=float, default=0.0005)
    ap.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        help="also serve Prometheus /metrics + /traces on this port "
        "(0 = kernel-assigned; reported as metrics_port= on the READY line)",
    )
    ap.add_argument(
        "--encode-backend",
        choices=("numpy", "pallas"),
        default=None,
        help="tail Encoder backend for writable opens (default: whatever "
        "the store's saved meta says; pallas needs jax on this host)",
    )
    ap.add_argument(
        "--target-p99-ms",
        type=float,
        default=None,
        help="enable the adaptive micro-batching window: the service tunes "
        "max_wait_s toward the largest value whose observed request p99 "
        "stays under this target",
    )
    args = ap.parse_args(argv)
    use_compile_cache()
    run(
        args.dir,
        host=args.host,
        port=args.port,
        read_only=args.read_only,
        max_batch=args.max_batch,
        max_wait_s=args.max_wait_s,
        target_p99_s=(
            None if args.target_p99_ms is None else args.target_p99_ms / 1e3
        ),
        metrics_port=args.metrics_port,
        encode_backend=args.encode_backend,
    )


if __name__ == "__main__":
    main()
