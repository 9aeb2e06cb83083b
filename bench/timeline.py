"""Run one cell with the program's tracer on and lay its spans on the
device trace.

    python -m bench.timeline --workload <name> --seed <n> --seconds <s>

A diagnostic beside ``bench.run``, which it reuses: the same set-up, the
same traffic and the same comparison of every answer, with the program's
own spans on in both processes (``REPRO_TRACE=1`` for the server child;
this process, the client, holds a ring large enough for the whole window)
and the profiler around exactly the window. Its latencies carry the
tracer's and the profiler's cost, so it reports no end-to-end metric. It
prints:

- ``spans``: per span name, count, seconds and self seconds over the
  window, both processes merged (what ``ctx["spans"]`` holds for the
  per-layer readers);
- ``store``: the store's copy counters over the window;
- ``metrics``: the per-layer metrics whose readers read those two;
- ``clock``: the offset that maps buffered span times onto the trace, and
  its largest residual, from the server's buffered spans and their
  profiler annotations (``repro.obs.trace.clock_offset``);
- ``idle``: the device's idle seconds in the window charged to the
  innermost program span open meanwhile, the server's before the
  client's, else ``none``;
- ``spell``: one line per spell of 50 ms or more with no answer: in each
  process the spans open across it (innermost first), its longest stretch
  with no span beginning or ending and the spans open over that stretch,
  and the spans that overlap it; and the client's collector passes in it.

With ``--out <path>``, everything also goes to that file as JSON.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from array import array

from bench import layout
from bench import trace as btrace
from bench.latency import percentile
from bench.run import (PLATFORM, ROOT, SRC, RunError, compare, log, serving,
                       window)

#: spans this process's ring holds: a 20 s get window books about 25,000 a
#: second (two per get, a few per RPC)
CLIENT_CAPACITY = 1 << 20
#: where the window's profile is kept until it has been read
TRACE_DIR = os.path.join(ROOT, ".bench_timeline")
#: the residual, in microseconds, a span's mapping onto the trace should
#: keep within
CLOCK_US = 100
#: a spell with no answer at least this long gets its own line
SPELL_S = 0.05
#: the program's span names start with one of these layers
PROGRAM_LAYERS = ("client", "rpc", "server", "service", "store", "kernel")
#: innermost first: device idle time goes to the first of these running
SERVER_ORDER = ("kernel.pack", "kernel.h2d", "kernel.dispatch", "kernel.wait",
                "kernel.d2h", "kernel.unpack", "kernel.decode_batch",
                "store.tokens", "store.cache_put", "store.decode",
                "store.multiget", "service.batch", "server.multiget",
                "server.get")
CLIENT_ORDER = ("rpc.multiget", "rpc.get", "client.coalesce",
                "client.multiget", "client.get")
#: the readers of ``ctx["spans"]`` and the store's copy counters
READERS = ("h2d_bytes_per_string", "copy_ms_per_batch",
           "store_prep_us_per_lookup", "client_wait_ms_per_get",
           "wire_ms_per_rpc")
#: the store's counters a reader may need, besides ``lookups``
STORE_COUNTERS = ("lookups", "real_tokens", "h2d_bytes", "d2h_bytes",
                  "device_strings", "host_strings", "decoded_strings",
                  "batches", "padded_rows")
_SPAN_FIELDS = {"repro_span_total": "count",
                "repro_span_seconds_total": "seconds",
                "repro_span_self_seconds_total": "self_seconds"}


class GcLog:
    """The start, end and generation of every collector pass of this
    process while entered, in preallocated arrays."""

    def __init__(self, capacity: int = 1 << 16):
        self.passes = array("q", [0]) * (3 * capacity)
        self.n = 0
        self._t0 = 0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        elif self.n * 3 < len(self.passes):
            k = self.n * 3
            self.passes[k] = self._t0
            self.passes[k + 1] = time.perf_counter_ns()
            self.passes[k + 2] = info["generation"]
            self.n += 1

    def __enter__(self) -> "GcLog":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)

    def intervals(self) -> list[tuple[int, int, int]]:
        p = self.passes
        return [(p[3 * i], p[3 * i + 1], p[3 * i + 2]) for i in range(self.n)]


# ------------------------------------------------------------- reductions
def span_totals(metrics: list[dict]) -> dict[str, dict]:
    """Per span name, count, seconds and self seconds from a registry
    snapshot's ``metrics`` list."""
    out: dict[str, dict] = {}
    for m in metrics:
        field = _SPAN_FIELDS.get(m["name"])
        if field:
            row = out.setdefault(m["labels"]["span"], {
                "count": 0, "seconds": 0.0, "self_seconds": 0.0})
            row[field] += m["value"]
    return out


def span_delta(after: dict, before: dict) -> dict:
    """``after - before`` per span name and field."""
    zero = {"count": 0, "seconds": 0.0, "self_seconds": 0.0}
    return {name: {k: v - before.get(name, zero)[k] for k, v in row.items()}
            for name, row in after.items()
            if row["count"] != before.get(name, zero)["count"]}


def merge_spans(*parts: dict) -> dict:
    out: dict[str, dict] = {}
    for part in parts:
        for name, row in part.items():
            acc = out.setdefault(name, {"count": 0, "seconds": 0.0,
                                        "self_seconds": 0.0})
            for k, v in row.items():
                acc[k] += v
    return out


def charge_idle(idle, layers) -> dict[str, float]:
    """Charge the ``idle`` intervals to ``layers`` (``(label, intervals)``,
    innermost first): each idle instant goes to the first layer running
    then, else to ``none``."""
    idle = btrace.union(idle)
    out = {}
    for label, intervals in layers:
        spans = btrace.union(intervals)
        got = btrace.total(btrace.intersect(idle, spans))
        if got > 0:
            out[label] = got
            idle = btrace.subtract(idle, spans)
    if btrace.total(idle) > 0:
        out["none"] = btrace.total(idle)
    return out


def order(names, first) -> list[str]:
    """``names`` with those in ``first`` first, in its order."""
    rank = {n: i for i, n in enumerate(first)}
    return sorted(set(names), key=lambda n: (rank.get(n, len(rank)), n))


def spells(done, start: float, end: float, least: float = SPELL_S):
    """``(from, to)`` of each stretch of at least ``least`` between two
    consecutive answers (times in one unit), in the window."""
    done = sorted(t for t in done if start <= t <= end)
    return [(a, b) for a, b in zip(done, done[1:]) if b - a >= least]


def open_across(spans, a, b, first, unit: float = 1.0) -> dict:
    """What one process ran across the spell ``[a, b]`` (``spans`` are
    ``(name, start, end)``; lengths are reported divided by ``unit``):
    ``across``, the spans open over all of it, innermost first (by
    ``first``); ``quiet``, the longest stretch of it in which none of the
    process's spans began or ended, and ``quiet_open``, the spans open over
    that stretch, innermost first (where the process stalled, the span it
    stalled in; none where it waited for work); ``overlapping``, the other
    spans that overlap it, with their overlap, largest first."""
    inside = [(n, s, e) for n, s, e in spans if s < b and e > a]
    edges = sorted({a, b} | {t for _, s, e in inside for t in (s, e)
                             if a < t < b})
    q0, q1 = max(zip(edges, edges[1:]), key=lambda g: g[1] - g[0])
    overlap: dict[str, float] = {}
    for n, s, e in inside:
        if s > a or e < b:
            overlap[n] = overlap.get(n, 0) + (min(e, b) - max(s, a)) / unit
    return {"across": order([n for n, s, e in inside if s <= a and e >= b],
                            first),
            "quiet": (q1 - q0) / unit,
            "quiet_open": order([n for n, s, e in inside
                                 if s <= q0 and e >= q1], first),
            "overlapping": sorted(overlap.items(),
                                  key=lambda kv: -kv[1])[:6]}


def read_trace(log_dir: str):
    """``(device_intervals, program_events, window)`` of a profile, in the
    profile's nanoseconds: the first device's op intervals, every host
    event named like a program span as ``(name, start, end)``, and the
    window's annotation."""
    import jax  # reads a file: no device is needed

    data = jax.profiler.ProfileData.from_file(btrace.find_xplane(log_dir))
    devices, events, win = [], [], None
    for plane in data.planes:
        if btrace.DEVICE_PLANE_RE.match(plane.name):
            devices.append([(ev.start_ns, ev.end_ns) for ln in plane.lines
                            if ln.name in (btrace.DEVICE_OP_LINE,
                                           btrace.DEVICE_ASYNC_LINE)
                            for ev in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == btrace.WINDOW:
                        win = (ev.start_ns, ev.end_ns)
                    elif ev.name.split(".", 1)[0] in PROGRAM_LAYERS:
                        events.append((ev.name, ev.start_ns, ev.end_ns))
    if win is None:
        raise RunError(f"no {btrace.WINDOW} annotation in {log_dir}")
    return (devices[0] if devices else []), events, win


def timeline(run: dict) -> dict:
    """The window's spans, clock mapping, idle charges and spells, from what
    :func:`measure` gathered (all times ``perf_counter_ns`` or the
    profile's nanoseconds)."""
    from repro.obs.trace import clock_offset

    device, events, (w0, w1) = run["device"], run["events"], run["window"]
    server = [(n, int(s * 1e3)) for n, s in run["server_spans"]]
    offset, residuals = clock_offset(
        server, [(n, int(s)) for n, s, _ in events])
    far = sum(r > CLOCK_US * 1e3 for r in residuals)
    idle = btrace.subtract([(w0, w1)], btrace.union(device))
    by_name: dict[str, list] = {}
    for n, s, e in events:
        by_name.setdefault(n, []).append((s, e))
    layers = [("server:" + n, by_name[n]) for n in order(by_name, SERVER_ORDER)]
    client: dict[str, list] = {}
    for n, _trace, _sid, _parent, s, e, _thread, _batch in run["client_spans"]:
        client.setdefault(n, []).append((s + offset, e + offset))
    layers += [("client:" + n, client[n]) for n in order(client, CLIENT_ORDER)]
    charged = charge_idle(idle, layers)
    # spells, on the program's clock
    server_spans = [(n, s - offset, e - offset) for n, s, e in events]
    client_spans = [(n, s, e) for n, *_, s, e, _t, _b in run["client_spans"]]
    found = []
    for a, b in spells(run["done_ns"], w0 - offset, w1 - offset,
                       SPELL_S * 1e9):
        gcs = [(g, (e - s) / 1e6) for s, e, g in run["gc"]
               if s < b and e > a]
        found.append({
            "at_s": (a - (w0 - offset)) / 1e9, "ms": (b - a) / 1e6,
            "server": open_across(server_spans, a, b, SERVER_ORDER, 1e6),
            "client": open_across(client_spans, a, b, CLIENT_ORDER, 1e6),
            # the client's collector passes of 1 ms or more, and how many
            # shorter ones ran in the spell
            "client_gc": [p for p in gcs if p[1] >= 1],
            "client_gc_short": sum(p[1] < 1 for p in gcs)})
    return {"clock": {
                "offset_ns": offset, "server_spans": len(server),
                "matched": len(residuals),
                "residual_us": residuals[-1] / 1e3 if residuals else None,
                "residual_p99_us": (residuals[int(0.99 * len(residuals))]
                                    / 1e3 if residuals else None),
                f"beyond_{CLOCK_US}_us": far},
            "window_s": (w1 - w0) / 1e9,
            "busy_s": (w1 - w0 - btrace.total(idle)) / 1e9,
            "idle_s": {k: v / 1e9 for k, v in
                       sorted(charged.items(), key=lambda kv: -kv[1])},
            "spells": found}


def per_layer(spans: dict, store: dict, root: str) -> dict:
    """The readers of ``ctx["spans"]`` and the copy counters."""
    ctx = {"counters": store, "spans": spans}
    out = {}
    for quantity in READERS:
        value = layout.metric_reader(quantity, root)(ctx)
        if value is not None:
            out[quantity] = value
    return out


def breakdown(spans: dict) -> dict:
    """``kernel.decode_batch`` split per batch (ms) and
    ``store.cache_put``'s share of ``store.decode``."""
    out = {}
    batches = spans.get("kernel.decode_batch", {}).get("count")
    if batches:
        out["kernel_ms_per_batch"] = {
            n: 1e3 * spans[n]["self_seconds"] / batches
            for n in ("kernel.decode_batch",) + SERVER_ORDER[:6]
            if n in spans}
    decode = spans.get("store.decode", {}).get("seconds")
    if decode:
        out["cache_put_share_of_decode"] = (
            spans.get("store.cache_put", {}).get("seconds", 0.0) / decode)
        out["tokens_share_of_decode"] = (
            spans.get("store.tokens", {}).get("seconds", 0.0) / decode)
    return out


# ---------------------------------------------------------------------- run
def measure(args, root: str, platform: str | None) -> dict:
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.obs import REGISTRY, TRACER

    env = os.environ.get("REPRO_TRACE")
    os.environ["REPRO_TRACE"] = "1"  # the server child traces too
    TRACER.enable(CLIENT_CAPACITY)
    spec = layout.resolve(args.workload, root)
    trace_dir = os.path.join(TRACE_DIR, "trace")
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    try:
        with serving(spec, args.seed, platform) as s:
            child, clients = s["child"], s["clients"]
            # every shard's server runs in the one server process, so the
            # first server's metrics and trace ring hold all their spans;
            # the store counters are each server's own
            shards = clients[0].backend.clients
            shard = shards[0]
            srv0, cli0 = shard.stats(metrics=True), REGISTRY.snapshot()
            store0 = [c.stats()["store"] for c in shards]
            TRACER.clear()  # the ring holds the window alone
            gc.freeze()
            child.call("window_start", trace_dir=trace_dir)
            with GcLog() as gclog:
                win = window(spec["traffic"], clients, s["sampler"],
                             args.seed, args.seconds)
            stopped = child.call("window_stop")
            gc.unfreeze()
            srv1, cli1 = shard.stats(metrics=True), REGISTRY.snapshot()
            store1 = [c.stats()["store"] for c in shards]
            dump = shard.trace_dump(1 << 30)
            got = compare(win, s["strings"])
            device_kind = s["device"]["kind"]
        device, events, window_ns = read_trace(trace_dir)
        client_spans = TRACER.spans()
    finally:
        TRACER.disable()
        if env is None:
            os.environ.pop("REPRO_TRACE")
        else:
            os.environ["REPRO_TRACE"] = env
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    spans = merge_spans(
        span_delta(span_totals(srv1["metrics"]["metrics"]),
                   span_totals(srv0["metrics"]["metrics"])),
        span_delta(span_totals(cli1["metrics"]), span_totals(cli0["metrics"])))
    store = {k: sum(b[k] - a[k] for a, b in zip(store0, store1))
             for k in STORE_COUNTERS if k in store1[0]}
    done = win.t_done[:win.sent]
    lat_ms = list(win.latencies_s() * 1e3)
    return {
        "workload": args.workload, "seed": args.seed,
        "device_kind": device_kind,
        "answers": {"requests": win.sent, "wrong": got["wrong"],
                    "failed": got["failed"],
                    "p50_ms_traced": percentile(lat_ms, 50),
                    "p90_ms_traced": percentile(lat_ms, 90)},
        "spans": spans, "store": store,
        "compiles": stopped["compiles"],
        "device": device, "events": events, "window": window_ns,
        "server_spans": [(sp["name"], sp["start_us"]) for t in dump
                         for sp in t["spans"]],
        "client_spans": client_spans,
        "done_ns": [int(t * 1e9) for t in done if t == t],
        "gc": gclog.intervals(),
    }


def report(run: dict, root: str) -> dict:
    out = {k: run[k] for k in ("workload", "seed", "device_kind", "answers",
                               "compiles", "spans", "store")}
    out["metrics"] = per_layer(run["spans"], run["store"], root)
    out["breakdown"] = breakdown(run["spans"])
    out.update(timeline(run))
    return out


def main(argv=None, root: str = ROOT, platform: str | None = PLATFORM
         ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        out = report(measure(args, root, platform), root)
    except (RuntimeError, layout.LayoutError, KeyError, OSError) as exc:
        print(f"timeline: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    log(f"answers: {json.dumps(out['answers'])}; compiles in the window "
        f"{out['compiles']}")
    for name, row in sorted(out["spans"].items()):
        log(f"spans: {name} {json.dumps(row)}")
    log(f"store: {json.dumps(out['store'])}")
    log(f"metrics: {json.dumps(out['metrics'])}")
    log(f"breakdown: {json.dumps(out['breakdown'])}")
    log(f"clock: {json.dumps(out['clock'])}")
    log(f"idle: window {out['window_s']:.3f} s, busy {out['busy_s']:.3f} s; "
        + json.dumps(out["idle_s"]))
    for sp in out["spells"]:
        log(f"spell: {json.dumps(sp)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
