"""Run one cell of ``BENCHMARK.json`` once.

    python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It generates the cell's corpus from
``--seed``, builds and saves the store on the host with the configuration's
settings, then starts the server child (``bench.serve``, the one process on
the chip; started after the build so that its start-up does not share the
host with the build), has it open and warm the store, fills the cache with
the traffic's warm-up requests, and then drives the traffic through
``repro.client.connect("tcp://...")`` for ``--seconds``. Every answer is
kept and, once the window has closed, compared with the source strings
(the plain reference). With ``--trace 1`` the child profiles exactly the
window and the run reports the cell's per-layer metrics; otherwise its
end-to-end metrics.

Progress goes to stdout line by line. The last stdout line is the result;
the last stderr lines are the numbers compared, each with its limit. A run
that finds no TPU, or fewer chips than the cell needs, or whose program is
missing, exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import queue
import shutil
import subprocess
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np

_T0 = time.perf_counter()

from bench import datasets, drive, hostwatch, layout, schedule  # noqa: E402
from bench.latency import beyond, percentile  # noqa: E402
from bench.roofline import peaks  # noqa: E402
from bench.serve import FAULTS, REPLY  # noqa: E402

ROOT = layout.ROOT
SRC = os.path.join(ROOT, "src")
#: the platform every measured run must be on
PLATFORM = "tpu"
#: the store the run builds and the trace it takes; deleted at exit
WORK = os.path.join(ROOT, ".bench_work")
#: JAX's persistent compilation cache, inside the checkout at a fixed path
CACHE = os.path.join(ROOT, ".jax_cache")
DEVICE_TIMEOUT_S = 300
OPEN_TIMEOUT_S = 900
CALL_TIMEOUT_S = 300


class RunError(RuntimeError):
    """The run cannot produce a result."""


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- the child
class Child:
    """The server process and its reply stream."""

    def __init__(self):
        env = dict(os.environ)
        env.pop("REPRO_NO_JAX", None)
        env["PYTHONPATH"] = os.pathsep.join(
            [SRC, ROOT] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                           else []))
        env["JAX_COMPILATION_CACHE_DIR"] = CACHE
        # the cache is this checkout's own: nothing to evict, and eviction
        # needs bookkeeping files that a size limit set outside expects
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        os.makedirs(CACHE, exist_ok=True)
        # the TPU runtime's logs stay in the checkout's work directory
        env.setdefault("TPU_LOG_DIR", os.path.join(WORK, "tpu_logs"))
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "bench.serve"], cwd=ROOT, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.replies: queue.Queue = queue.Queue()
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            if line.startswith(REPLY):
                self.replies.put(json.loads(line[len(REPLY):]))
            else:
                sys.stderr.write("[server] " + line)
        self.replies.put(None)   # the child's stdout closed: it is gone

    def reply(self, timeout: float) -> dict:
        try:
            out = self.replies.get(timeout=timeout)
        except queue.Empty:
            raise RunError(f"the server did not answer in {timeout:.0f} s")
        if out is None:
            raise RunError(f"the server exited (code {self.proc.wait()})")
        if "error" in out:
            raise RunError(f"the server failed: {out['error']}")
        return out

    def call(self, cmd: str, timeout: float = CALL_TIMEOUT_S, **kw) -> dict:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")
        self.proc.stdin.flush()
        return self.reply(timeout)

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write(json.dumps({"cmd": "exit"}) + "\n")
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=60)
        self.reader.join(timeout=10)


# ----------------------------------------------------------------- the data
def build_store(config: dict, seed: int, path: str) -> dict:
    """Generate the corpus and build, save the store (host only)."""
    from repro.store import CompressedStringStore

    t0 = time.perf_counter()
    strings = datasets.generate(config["dataset"], config["raw_mib"] << 20,
                                seed, config.get("dataset_shape"))
    t1 = time.perf_counter()
    settings = dict(config["store"])
    store = CompressedStringStore.build(
        strings, codec=config["codec"], backend="numpy",
        sample_bytes=settings.pop("sample_bytes"), **settings)
    store.save(path)
    t2 = time.perf_counter()
    return {"strings": strings, "raw_bytes": sum(map(len, strings)),
            "generate_s": t1 - t0, "build_s": t2 - t1,
            "compressed_bytes": int(store.corpus.compressed_bytes),
            "entries": int(store.dictionary.num_entries)}


# ------------------------------------------------------------ the counters
def counters(client) -> dict:
    """The server's counters from the stats RPC, flattened."""
    stats = client.backend.clients[0].stats()
    store, ops = stats["store"], stats["ops"]
    return {
        "lookups": store["lookups"],
        "decoded_strings": store["decoded_strings"],
        "decoded_bytes": store["decoded_bytes"],
        "batches": store["batches"],
        "padded_rows": store["padded_rows"],
        "jit_shapes": len(store["jit_shapes"]),
        "cache_hits": store["cache"]["hits"],
        "cache_misses": store["cache"]["misses"],
        "rpc_multiget": ops.get("multiget", 0),
        "rpc_get": ops.get("get", 0),
        "memory_bytes": store["memory_bytes"],
    }


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


# ----------------------------------------------------------------- traffic
def warm(clients, sampler, seed, warm_cfg: dict) -> drive.Window:
    """The traffic's warm-up multigets, spread over ``clients``; their
    answers are checked with the window's."""
    n, fanout = int(warm_cfg.get("requests", 0)), int(warm_cfg.get(
        "fanout", 1024))
    per = [schedule.requests(sampler, schedule.stream(seed, schedule.WARM, k),
                             (n + len(clients) - 1 - k) // len(clients),
                             fanout) for k in range(len(clients))]
    return drive.closed_loop(clients, per, None)


def window(traffic: dict, clients, sampler, seed: int, seconds: float,
           stream_base: int = schedule.WINDOW) -> drive.Window:
    if traffic["loop"] == "closed":
        fanout = int(traffic["fanout"])
        per = [schedule.requests(
            sampler, schedule.stream(seed, stream_base, k),
            int(traffic["requests_per_client"]), fanout)
            for k in range(len(clients))]
        return drive.closed_loop(clients, per, seconds)
    if traffic["op"] != "get":
        raise RunError(f"open loop supports op get, not {traffic['op']!r}")
    rng = schedule.stream(seed, stream_base)
    at = schedule.arrivals(float(traffic["rate"]), rng, seconds)
    ids = sampler.draw(rng, at.size).tolist()
    return drive.open_loop(clients[0], ids, at, seconds)


# -------------------------------------------------------------- comparison
def compare(win: drive.Window, strings) -> dict:
    """Every answer of ``win`` against the source strings: wrong strings,
    failed requests, strings right."""
    wrong = failed = right = 0
    for k in range(win.sent):
        if win.failed(k):
            failed += 1
            continue
        ids, got = win.request_ids(k), win.answer(k)
        if len(got) != len(ids):
            wrong += len(ids)
            continue
        for i, s in zip(ids, got):
            if s == strings[i]:
                right += 1
            else:
                wrong += 1
    return {"wrong": wrong, "failed": failed, "right": right}


# --------------------------------------------------------------------- run
def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="break the served path on purpose (lossy8 is the "
                    "control); such a run must come out not correct")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


@contextmanager
def serving(spec: dict, seed: int, platform: str | None, trace: bool = False,
            fault: str | None = None):
    """Set-up of one run, up to warm caches: yields the server child, the
    connected clients, the id sampler, the source strings and what set-up
    measured. Closes the clients, stops the child and deletes the work
    directory on exit."""
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if not os.path.isfile(os.path.join(SRC, "repro", "store", "store.py")):
        raise RunError(f"the program is missing: no {SRC}/repro")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.client import connect

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    store_dir = os.path.join(WORK, "store")
    child = None
    clients: list = []
    try:
        built = build_store(config, seed, store_dir)
        strings = built.pop("strings")
        log(f"corpus: {config['dataset']} seed={seed} {len(strings)} "
            f"strings, {built['raw_bytes']} raw bytes, ratio "
            f"{built['raw_bytes'] / built['compressed_bytes']:.4f}, "
            f"{built['entries']} dictionary entries; generate "
            f"{built['generate_s']:.2f} s, build+save {built['build_s']:.2f} s")
        t_child = time.perf_counter()
        child = Child()
        device = child.reply(DEVICE_TIMEOUT_S)["device"]
        log(f"device: {device} ({time.perf_counter() - t_child:.2f} s "
            f"after the server started)")
        if platform is not None and device["platform"] != platform:
            raise RunError(f"no {platform} found: JAX reports {device}")
        if device["count"] < int(cell["chips"]):
            raise RunError(f"the cell needs {cell['chips']} chips, JAX "
                           f"reports {device['count']}")
        t_open = time.perf_counter()
        opened = child.call("open", OPEN_TIMEOUT_S, path=store_dir,
                            trace=trace, fault=fault)
        log(f"server: ready {time.perf_counter() - t_open:.2f} s after the "
            f"build (open {opened['open_s']:.2f} s, bucket shapes "
            f"{opened['compile_s']:.2f} s), bucket caps "
            f"{opened['bucket_caps']}, first batch per shape "
            f"{opened['first_batch_s']}, compile cache "
            f"{opened['compile_cache']} ({opened['cache_hits']} hits, "
            f"{opened['cache_misses']} misses), store device "
            f"{opened['store_device']}")
        url = f"tcp://127.0.0.1:{opened['port']}"
        clients = [connect(url) for _ in range(int(traffic.get("clients", 1)))]
        sampler = schedule.IdSampler(traffic["ids"], len(strings))
        # strings decoded from here on serve answers that are compared: the
        # server's own shape warm-up before it is left out
        before_warm = counters(clients[0])
        t_warm = time.perf_counter()
        warm_cfg = traffic.get("warm", {})
        warm_wins = [warm(clients if len(clients) > 1 else clients * 4,
                          sampler, seed, warm_cfg)]
        if warm_cfg.get("loop_seconds"):
            warm_wins.append(window(traffic, clients, sampler, seed,
                                    float(warm_cfg["loop_seconds"]),
                                    stream_base=schedule.WARM + 10))
        log(f"warm: {sum(w.sent for w in warm_wins)} requests in "
            f"{time.perf_counter() - t_warm:.2f} s")
        yield {"child": child, "clients": clients, "sampler": sampler,
               "strings": strings, "built": built, "device": device,
               "warm": warm_wins, "before_warm": before_warm}
    finally:
        for c in clients:
            c.close()
        if child is not None:
            child.stop()
        shutil.rmtree(WORK, ignore_errors=True)


def run(args, root: str, platform: str | None, t0: float) -> dict:
    spec = layout.resolve(args.workload, root)
    with serving(spec, args.seed, platform, bool(args.trace),
                 args.fault) as s:
        child, clients = s["child"], s["clients"]
        before = counters(clients[0])
        # the reference strings and set-up's objects are the benchmark's:
        # keep them out of the collections the client pays for in the window
        gc.freeze()
        trace_dir = os.path.join(WORK, "trace") if args.trace else None
        child.call("window_start", trace_dir=trace_dir)
        setup_s = time.perf_counter() - t0
        cpu0 = hostwatch.cpu_times()
        with hostwatch.GcWatch() as client_gc:
            win = window(spec["traffic"], clients, s["sampler"], args.seed,
                         args.seconds)
        cpu1 = hostwatch.cpu_times()
        stopped = child.call("window_stop")
        gc.unfreeze()
        after = counters(clients[0])
    return {"spec": spec, "device": s["device"], "built": s["built"],
            "strings": s["strings"], "warm": s["warm"], "window": win,
            "setup_s": setup_s, "stopped": stopped,
            "counts": delta(after, before),
            "host": {"client_gc": client_gc.summary(),
                     "server_gc": stopped.get("gc"),
                     "steal_s": hostwatch.steal_s(cpu0, cpu1)},
            "memory_bytes": after["memory_bytes"],
            "kernel_strings": after["decoded_strings"]
            - s["before_warm"]["decoded_strings"]}


def quarters(win: drive.Window) -> list[int]:
    """Requests answered in each quarter of the window (a window that slows
    down as it goes shows here)."""
    done = win.t_done[:win.sent] - win.start
    q = np.floor(done / (win.seconds / 4)).clip(0, 3)
    return [int(np.sum(q == i)) for i in range(4)]


def stalls(win: drive.Window, segments: int = 10) -> dict:
    """Where a window's latency came from: the longest spells with no
    answer, and the p90 of the requests due in each tenth of the window."""
    done = np.sort(win.t_done[:win.sent][np.isfinite(win.t_done[:win.sent])])
    gaps = np.sort(np.diff(done))[::-1][:3] * 1e3 if done.size > 1 else []
    lat = win.latencies_s() * 1e3
    due = win.t_ref[:win.sent] - win.start
    seg = np.floor(due / (win.seconds / segments)).clip(0, segments - 1)
    p90 = [percentile(list(lat[seg == i]), 90) if np.any(seg == i) else None
           for i in range(segments)]
    return {"longest_gaps_ms": [round(float(g), 3) for g in gaps],
            "p90_ms_per_tenth": [None if v is None else round(float(v), 3)
                                 for v in p90]}


def report(args, res: dict, root: str) -> tuple[dict, dict]:
    """The result line and the numbers compared."""
    spec, win, stopped = res["spec"], res["window"], res["stopped"]
    strings = res["strings"]
    got = compare(win, strings)
    warm_got = [compare(w, strings) for w in res["warm"]]
    checks = {
        "wrong_strings": {"value": got["wrong"] + sum(
            g["wrong"] for g in warm_got), "max": 0},
        "failed_requests": {"value": got["failed"] + sum(
            g["failed"] for g in warm_got), "max": 0},
        "kernel_strings": {"value": res["kernel_strings"], "min": 1},
    }
    correct = all(c["value"] <= c["max"] if "max" in c else
                  c["value"] >= c["min"] for c in checks.values())
    lat_ms = list(win.latencies_s() * 1e3) or [math.inf]
    tails = {q: percentile(lat_ms, q) for q in (50, 90, 95, 99)}
    log(f"window: {win.sent} requests in {win.seconds:.3f} s, "
        f"{got['right']} strings right; "
        + ", ".join(f"p{q} {v:.3f} ms ({beyond(lat_ms, v)} beyond)"
                    for q, v in tails.items())
        + f"; late sends {win.late}; answers per quarter of the window "
        f"{quarters(win)}")
    log(f"window host: {json.dumps(res['host'])}; "
        f"{json.dumps(stalls(win))}")
    counts = res["counts"]
    log(f"window counters: {json.dumps(counts)}; compile events in the "
        f"window {stopped['compiles']}, new decode shapes "
        f"{counts['jit_shapes']}")
    dev = res["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": stopped["peak_bytes_in_use"] or 0}
    values = {
        # a failure lies beyond every limit; JSON has no infinity
        "read_p50_ms": min(tails[50], sys.float_info.max),
        "read_p90_ms": min(tails[90], sys.float_info.max),
        "strings_per_s": got["right"] / win.seconds,
        "bytes_per_raw_byte": (res["memory_bytes"]
                               + (stopped["bytes_in_use"] or 0))
        / res["built"]["raw_bytes"],
        "setup_s": res["setup_s"],
    }
    out = {"correct": correct, "attempted": win.sent,
           "failed": got["failed"]}
    metrics = {}
    if not args.trace:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        trace = stopped["trace"]
        log(f"trace: planes {json.dumps(trace['inventory'])}")
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        ctx = {"counters": {**counts, "real_tokens": stopped["real_tokens"]},
               "trace": trace, "peaks": peaks(dev["kind"], root),
               "latency": {f"p{q}_ms": v for q, v in tails.items()}}
        for m in spec["per_layer"]:
            value = layout.metric_reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["breakdown"] = {
            "device_ops": [list(kv) for kv in trace["device_ops"]],
            "idle_gaps": [list(kv) for kv in trace["idle_gaps"]]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = checks
    return out, checks


def main(argv=None, root: str = ROOT, platform: str | None = PLATFORM
         ) -> int:
    t0 = _T0 if argv is None else time.perf_counter()
    args = _parse(argv)
    try:
        res = run(args, root, platform, t0)
        out, checks = report(args, res, root)
    except (RuntimeError, layout.LayoutError, KeyError, OSError) as exc:
        print(f"bench: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    for name, c in checks.items():
        rule = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name}: {c['value']} (limit {rule})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
