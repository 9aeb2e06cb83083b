"""The benchmark's server process: the one process that touches the chip.

``bench.run`` starts it as ``python -m bench.serve`` and talks to it in
JSON lines: commands on stdin, replies on stdout after :data:`REPLY`.

1. At start it reports the devices JAX sees, before any other work.
2. ``open``: turns on the program's compile cache, opens the saved store
   read-only through ``ShardServer.from_dir`` (the program's serving path),
   serves it on a free port in this process, and compiles every decode
   bucket shape of the store by one ``multiget`` that hits each bucket.
   Under ``trace`` it also records host annotations around its calls into
   each layer (``bench.trace.HOST_LAYERS``) and counts the real tokens that
   reach the decode; a ``fault`` breaks the decode on purpose (the control
   and the harness tests).
3. ``window_start`` / ``window_stop``: bracket the measured window: count
   compile events in it, and under ``trace`` wrap exactly it in the
   profiler. ``window_stop`` replies with the device's memory, the compile
   count, the token count and the trace reduction.
4. ``exit``: closes the server.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

from bench.hostwatch import GcWatch

REPLY = "BENCH_REPLY "
#: the commands the parent may send besides ``exit``
COMMANDS = ("open", "window_start", "window_stop")

#: ways to break the served path on purpose: ``lossy8`` keeps 8 of each
#: dictionary row's 16 bytes (the control: a narrower gather than the
#: format's exact bytes); ``flip`` alters one byte of the first string of
#: every decode batch where it is produced; ``half`` decodes the first half
#: of every decode batch and leaves the rest empty; ``drop`` fails every
#: 16th multiget, so its answer never comes; ``bypass`` answers every
#: multiget from a table decoded on the host at open, so the answers are
#: right but no string the run compares was decoded by the kernel
FAULTS = ("lossy8", "flip", "half", "drop", "bypass")
#: compile events counted inside the window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
#: persistent compile cache lookups, counted while the store opens
CACHE_EVENTS = ("/jax/compilation_cache/cache_hits",
                "/jax/compilation_cache/cache_misses")


def reply(obj: dict) -> None:
    sys.stdout.write(REPLY + json.dumps(obj) + "\n")
    sys.stdout.flush()


def _annotate(obj, attr: str, name: str) -> None:
    """Record a host annotation ``name`` around every call of
    ``obj.<attr>``."""
    import jax

    inner = getattr(obj, attr)

    def wrapped(*args, **kw):
        with jax.profiler.TraceAnnotation(name):
            return inner(*args, **kw)

    setattr(obj, attr, wrapped)


def _count_tokens(device, counts: dict) -> None:
    inner = device.multiget_decode

    def wrapped(token_lists, *args, **kw):
        counts["real_tokens"] += sum(len(t) for t in token_lists)
        return inner(token_lists, *args, **kw)

    device.multiget_decode = wrapped


def _break(store, fault: str) -> None:
    device = store._device
    if fault == "lossy8":
        dd = device.dd
        device.dd = dataclasses.replace(dd, mat16=dd.mat16.at[:, 8:].set(0))
    elif fault == "flip":
        inner = device.multiget_decode

        def wrapped(*args, **kw):
            out = inner(*args, **kw)
            if out and out[0]:
                out[0] = bytes([out[0][0] ^ 1]) + out[0][1:]
            return out

        device.multiget_decode = wrapped
    elif fault == "half":
        inner = device.multiget_decode

        def wrapped(token_lists, *args, **kw):
            keep = (len(token_lists) + 1) // 2
            out = list(inner(token_lists[:keep], *args, **kw))
            return out + [b""] * (len(token_lists) - keep)

        device.multiget_decode = wrapped
    elif fault == "drop":
        inner, calls = store.multiget, [0]

        def wrapped(ids):
            calls[0] += 1
            if calls[0] % 16 == 0:
                raise RuntimeError("answer dropped on purpose")
            return inner(ids)

        store.multiget = wrapped
    elif fault == "bypass":
        table = store.scan(0, store.n_strings)
        store.multiget = lambda ids: [table[int(i)] for i in ids]
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")


def _warm_ids(store) -> list[int]:
    """One id per decode bucket: the multiget that compiles every bucket
    shape the store can launch."""
    import numpy as np

    counts = store.corpus.token_counts()
    bucket = np.searchsorted(store.bucket_caps, counts, side="left")
    _, first = np.unique(bucket, return_index=True)
    return sorted(int(i) for i in first)


class Server:
    def __init__(self):
        import jax

        self.jax = jax
        self.compiles = 0
        self.counts = {"real_tokens": 0, **{e: 0 for e in CACHE_EVENTS}}
        self.server = None
        self.trace_dir = None
        self.window_ann = None
        self.gc_watch = None

        def listen(event, _duration, **_kw):
            if event in COMPILE_EVENTS:
                self.compiles += 1

        def count(event, **_kw):
            if event in CACHE_EVENTS:
                self.counts[event] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(count)

    def devices(self) -> dict:
        devs = self.jax.devices()
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    def open(self, path: str, trace: bool, fault: str | None) -> dict:
        from repro.kernels.cache import use_compile_cache
        from repro.net.shard_server import ShardServer

        cache = use_compile_cache()
        t0 = time.perf_counter()
        self.server = ShardServer.from_dir(path, read_only=True)
        self.server.start()
        store = self.server.store
        if store.backend != "jax":
            raise RuntimeError(f"store resolved backend {store.backend!r}, "
                               "not jax")
        if trace:
            _count_tokens(store._device, self.counts)
            _annotate(self.server, "dispatch", "bench.rpc")
            _annotate(store, "multiget", "bench.store.multiget")
            _annotate(store, "_decode_misses", "bench.store.decode_misses")
            _annotate(store._device, "multiget_decode",
                      "bench.kernel.multiget_decode")
            _annotate(store._device, "decode_batch",
                      "bench.kernel.decode_batch")
        if fault:
            _break(store, fault)
        t1 = time.perf_counter()
        store.multiget(_warm_ids(store))
        t2 = time.perf_counter()
        snap = store.stats_snapshot()
        return {"port": self.server.port, "open_s": t1 - t0,
                "compile_s": t2 - t1, "compile_cache": cache,
                "cache_hits": self.counts[CACHE_EVENTS[0]],
                "cache_misses": self.counts[CACHE_EVENTS[1]],
                "bucket_caps": snap["bucket_caps"],
                "first_batch_s": snap["first_batch_s"],
                "store_device": snap.get("device")}

    def window_start(self, trace_dir: str | None) -> dict:
        self.trace_dir = trace_dir
        if trace_dir:
            opts = self.jax.profiler.ProfileOptions()
            # tracing every Python call would slow the host path measured
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.window_ann = self.jax.profiler.TraceAnnotation(
            "bench.window")
        self.window_ann.__enter__()
        self.compiles0 = self.compiles
        self.tokens0 = self.counts["real_tokens"]
        self.gc_watch = GcWatch().__enter__()
        return {}

    def window_stop(self) -> dict:
        self.window_ann.__exit__(None, None, None)
        self.gc_watch.__exit__()
        compiles = self.compiles - self.compiles0
        tokens = self.counts["real_tokens"] - self.tokens0
        trace = None
        if self.trace_dir:
            from bench.trace import reduce_trace

            self.jax.profiler.stop_trace()
            trace = reduce_trace(self.trace_dir)
        dev = self.jax.devices()[0]
        mem = dev.memory_stats() or {}
        return {"compiles": compiles, "real_tokens": tokens, "trace": trace,
                "gc": self.gc_watch.summary(),
                "bytes_in_use": mem.get("bytes_in_use"),
                "peak_bytes_in_use": mem.get("peak_bytes_in_use")}

    def close(self) -> None:
        if self.server is not None:
            self.server.close()


def main() -> int:
    srv = Server()
    reply({"device": srv.devices()})
    try:
        for line in sys.stdin:
            cmd = json.loads(line)
            op = cmd.pop("cmd")
            try:
                if op == "exit":
                    break
                if op not in COMMANDS:
                    raise ValueError(f"unknown command {op!r}")
                out = getattr(srv, op)(**cmd)
            except Exception as exc:  # the parent reports it and stops
                reply({"error": f"{op}: {exc!r}"})
                return 1
            reply(out)
    finally:
        srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
