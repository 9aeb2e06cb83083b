"""The benchmark's server process: the one process that touches the chip.

``bench.run`` starts it as ``python -m bench.serve`` and talks to it in
JSON lines: commands on stdin, replies on stdout after :data:`REPLY`.

1. At start it reports the devices JAX sees, before any other work.
2. ``open``: turns on the program's compile cache, opens each saved shard
   directory read-only through ``ShardServer.from_dir`` (the program's
   serving path; a ``shard-000k`` directory lands on its own device through
   ``open_shard``), serves each on a free port in this process, checks that
   every store decodes on the ``jax`` backend and that N shards sit on N
   distinct devices, and compiles every decode bucket shape of each store
   by one ``multiget`` that hits each bucket. Under ``trace`` it also
   records host annotations around its calls into each layer
   (``bench.trace.HOST_LAYERS``) and counts the real tokens that reach the
   decode; a ``fault`` breaks every shard on purpose (the control and the
   harness tests).
3. ``window_start`` / ``window_stop``: bracket the measured window: count
   compile events in it, and under ``trace`` wrap exactly it in the
   profiler. ``window_stop`` replies with the memory of the devices that
   hold a shard, the compile count, the token count and the trace
   reduction.
4. ``exit``: closes the servers.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import threading
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
#: right but no string the run compares was decoded by the kernel;
#: ``oneshard`` (two shards or more) answers every shard's local ids from
#: the first shard's store, as if the fan-out to the other chips were left
#: out
FAULTS = ("lossy8", "flip", "half", "drop", "bypass", "oneshard")
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


def _count_tokens(device, counts: dict, lock) -> None:
    inner = device.multiget_decode

    def wrapped(token_lists, *args, **kw):
        n = sum(len(t) for t in token_lists)
        with lock:   # every shard's batch thread adds to one count
            counts["real_tokens"] += n
        return inner(token_lists, *args, **kw)

    device.multiget_decode = wrapped


def _break(store, fault: str, stores: list) -> None:
    """Break ``store``, one of the served ``stores``, as ``fault`` says."""
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
    elif fault == "oneshard":
        if len(stores) < 2:
            raise RuntimeError("fault oneshard needs two shards or more")
        # ``stores[0]`` is broken first and keeps its own method
        store.multiget = stores[0].multiget
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
        self.count_lock = threading.Lock()
        self.servers: list = []
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

    def open(self, paths: list[str], trace: bool, fault: str | None
             ) -> dict:
        """Serve each store directory of ``paths`` (one per shard, in shard
        order) from its own ``ShardServer`` in this process."""
        from repro.kernels.cache import use_compile_cache
        from repro.net.shard_server import ShardServer

        cache = use_compile_cache()
        t0 = time.perf_counter()
        for path in paths:
            self.servers.append(ShardServer.from_dir(path, read_only=True))
            self.servers[-1].start()
        stores = [srv.store for srv in self.servers]
        backends = [store.backend for store in stores]
        if any(b != "jax" for b in backends):
            raise RuntimeError(f"stores resolved backends {backends}, not jax")
        devices = [store._device.device for store in stores]
        if len(set(devices)) != len(devices):
            raise RuntimeError(f"{len(stores)} shards on devices {devices}: "
                               "each shard needs a device of its own")
        for srv, store in zip(self.servers, stores):
            if trace:
                _count_tokens(store._device, self.counts, self.count_lock)
                _annotate(srv, "dispatch", "bench.rpc")
                _annotate(store, "multiget", "bench.store.multiget")
                _annotate(store, "_decode_misses",
                          "bench.store.decode_misses")
                _annotate(store._device, "multiget_decode",
                          "bench.kernel.multiget_decode")
                _annotate(store._device, "decode_batch",
                          "bench.kernel.decode_batch")
            if fault:
                _break(store, fault, stores)
        t1 = time.perf_counter()
        for store in stores:
            store.multiget(_warm_ids(store))
        t2 = time.perf_counter()
        shards = []
        for srv, store in zip(self.servers, stores):
            snap = store.stats_snapshot()
            shards.append({"port": srv.port,
                           "n_strings": int(store.n_strings),
                           "bucket_caps": snap["bucket_caps"],
                           "first_batch_s": snap["first_batch_s"],
                           "device": snap.get("device")})
        return {"shards": shards, "open_s": t1 - t0, "compile_s": t2 - t1,
                "compile_cache": cache,
                "cache_hits": self.counts[CACHE_EVENTS[0]],
                "cache_misses": self.counts[CACHE_EVENTS[1]]}

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
        """The window's counts and trace, and the memory of the devices
        that hold a shard: bytes in use summed over them, the peak of the
        fullest."""
        self.window_ann.__exit__(None, None, None)
        self.gc_watch.__exit__()
        compiles = self.compiles - self.compiles0
        tokens = self.counts["real_tokens"] - self.tokens0
        trace = None
        if self.trace_dir:
            from bench.trace import reduce_trace

            self.jax.profiler.stop_trace()
            trace = reduce_trace(self.trace_dir)
        return {"compiles": compiles, "real_tokens": tokens, "trace": trace,
                "gc": self.gc_watch.summary(),
                **device_memory(srv.store._device.device
                                for srv in self.servers)}

    def close(self) -> None:
        for srv in self.servers:
            srv.close()


def device_memory(devices) -> dict:
    """``bytes_in_use`` summed over ``devices``, each counted once, and
    ``peak_bytes_in_use`` of the fullest; None where a device reports
    none (the CPU)."""
    mems = [dev.memory_stats() or {} for dev in dict.fromkeys(devices)]
    use = [m.get("bytes_in_use") for m in mems]
    peak = [m.get("peak_bytes_in_use") for m in mems]
    return {"bytes_in_use": None if None in use else sum(use),
            "peak_bytes_in_use": None if None in peak or not peak
            else max(peak)}


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
