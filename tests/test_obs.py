"""Tests for repro.obs — metrics registry, tracing, and export surfaces.

Histogram percentile/merge correctness (merged p99 == pooled p99 within one
bucket), registry identity-merge + Prometheus rendering invariants (bucket
counts sum to the op counter), tracer span parentage and the bounded
slow-request ring, protocol trace-header round-trips, version compat in both
directions (old client -> new server, new client -> old server via the caps
probe), and end-to-end span chains across live ``shard://`` and ``tcp://``
multigets including the ``stats`` RPC metrics extension and ``trace_dump``
RPC.

Everything here is stdlib + numpy, so the minimal-numpy CI job runs it.
"""

import gc
import glob
import json
import math
import os
import re
import socket
import subprocess
import sys
import threading
import time
import tracemalloc
import urllib.error
import urllib.request
from bisect import bisect_left

import numpy as np
import pytest

from repro.client import connect
from repro.data.synth import load_dataset
from repro.distributed import save_sharded
from repro.net import RemoteShardClient, ShardServer
from repro.net import protocol as P
from repro.obs import (
    TRACER,
    Counter,
    Histogram,
    MetricsRegistry,
    TraceContext,
    Tracer,
    merge_hist_states,
    new_trace_id,
    start_metrics_server,
    summarize_hist_state,
)
from repro.store import CompressedStringStore

SAMPLE = 1 << 14


@pytest.fixture(scope="module")
def titles():
    return load_dataset("book_titles", SAMPLE)


@pytest.fixture(scope="module")
def sharded_dir(titles, tmp_path_factory):
    store = CompressedStringStore.build(
        titles, sample_bytes=SAMPLE, strings_per_segment=256
    )
    d = str(tmp_path_factory.mktemp("obs") / "shards")
    save_sharded(store, d, 2)
    return d


@pytest.fixture()
def traced():
    """Tracing on for one test (it is off by default), from an empty ring."""
    TRACER.enable()
    TRACER.clear()
    yield TRACER
    TRACER.disable()


@pytest.fixture()
def server(sharded_dir):
    s = ShardServer.from_dir(os.path.join(sharded_dir, "shard-0000")).start()
    yield s
    s.close()


@pytest.fixture()
def tcp_cluster(sharded_dir):
    servers = [
        ShardServer.from_dir(os.path.join(sharded_dir, f"shard-{k:04d}")).start()
        for k in range(2)
    ]
    yield servers
    for s in servers:
        s.close()


def _bucket_interval(bounds, value):
    """The ``(lo, hi]`` histogram bucket a value falls in."""
    i = bisect_left(bounds, value)
    lo = bounds[i - 1] if i else 0.0
    hi = bounds[i] if i < len(bounds) else bounds[-1] * 2
    return lo, hi


def _assert_parentage(trace):
    """Every span is the root or a child of another span in the trace."""
    span_ids = {s["span_id"] for s in trace["spans"]}
    roots = [s for s in trace["spans"] if s["parent_id"] == 0]
    assert len(roots) == 1, f"expected one root span, got {roots}"
    for s in trace["spans"]:
        if s["parent_id"] != 0:
            assert s["parent_id"] in span_ids, f"orphaned span {s}"
        assert s["trace_id"] == trace["trace_id"]


# ------------------------------------------------------------------ histogram
def test_histogram_percentiles_within_one_bucket():
    h = Histogram("t_lat_us")
    values = [3.0, 5.0, 9.0, 17.0, 33.0, 100.0, 1000.0, 5000.0]
    for v in values:
        h.record(v)
    s = h.summary()
    assert s["count"] == len(values)
    assert s["mean_us"] == pytest.approx(sum(values) / len(values))
    for pct, key in ((50.0, "p50_us"), (99.0, "p99_us"), (99.9, "p999_us")):
        rank = max(1, math.ceil(len(values) * pct / 100.0))
        true = sorted(values)[rank - 1]
        lo, hi = _bucket_interval(h.bounds, true)
        assert lo < s[key] <= hi, f"{key}: {s[key]} not in ({lo}, {hi}]"


def test_histogram_overflow_bucket_and_count():
    h = Histogram("t_over_us")
    h.record(1e12)  # way past the last bound
    h.record(0.5)  # below the first bound
    assert h.count == 2
    state = h.state()
    assert state["counts"][-1] == 1  # overflow
    assert state["counts"][0] == 1  # first finite bucket
    assert len(state["counts"]) == len(state["bounds"]) + 1


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(1.0, 1.0, 2.0))


def test_merged_percentiles_equal_pooled_within_one_bucket():
    rng = np.random.default_rng(42)
    a = rng.lognormal(mean=4.0, sigma=1.5, size=500)
    b = rng.lognormal(mean=6.0, sigma=1.0, size=300)
    ha, hb, pooled = Histogram("m"), Histogram("m"), Histogram("m")
    for v in a:
        ha.record(float(v))
    for v in b:
        hb.record(float(v))
    for v in np.concatenate([a, b]):
        pooled.record(float(v))
    merged = merge_hist_states([ha.state(), hb.state()])
    # the merge is exact: merged counts equal a histogram of pooled samples
    assert merged["counts"] == pooled.state()["counts"]
    assert merged["sum"] == pytest.approx(pooled.sum)
    ms, ps = summarize_hist_state(merged), pooled.summary()
    for k in ("p50_us", "p99_us", "p999_us", "count", "mean_us"):
        assert ms[k] == pytest.approx(ps[k]), k
    # and the merged p99 lands in the same bucket as the true sample p99
    samples = np.sort(np.concatenate([a, b]))
    true_p99 = float(samples[math.ceil(0.99 * len(samples)) - 1])
    lo, hi = _bucket_interval(merged["bounds"], true_p99)
    assert lo < ms["p99_us"] <= hi


def test_merge_rejects_mismatched_bounds():
    a = Histogram("a", bounds=(1.0, 2.0)).state()
    b = Histogram("b", bounds=(1.0, 4.0)).state()
    with pytest.raises(ValueError):
        merge_hist_states([a, b])


def test_merge_and_summary_of_nothing():
    assert merge_hist_states([]) is None
    assert merge_hist_states([None, {}]) is None
    empty = summarize_hist_state(None)
    assert empty == {"p50_us": 0.0, "p99_us": 0.0, "p999_us": 0.0,
                     "count": 0, "mean_us": 0.0}


def test_counter_exact_under_concurrency():
    c = Counter("c_total")
    n_threads, per_thread = 8, 10_000

    def work():
        for _ in range(per_thread):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread


# ------------------------------------------------------------------- registry
def test_registry_merges_same_identity_instruments():
    reg = MetricsRegistry()
    h1 = reg.register(Histogram("repro_x_lat_us", labels={"backend": "numpy"}))
    h2 = reg.register(Histogram("repro_x_lat_us", labels={"backend": "numpy"}))
    other = reg.register(Histogram("repro_x_lat_us", labels={"backend": "pallas"}))
    c = reg.counter("repro_x_total", backend="numpy")
    for v in (10.0, 20.0):
        h1.record(v)
        c.inc()
    h2.record(40.0)
    c.inc()
    other.record(7.0)
    series = {
        (m["name"], tuple(sorted(m["labels"].items()))): m
        for m in reg.snapshot()["metrics"]
    }
    merged = series[("repro_x_lat_us", (("backend", "numpy"),))]
    assert sum(merged["counts"]) == 3
    assert merged["sum"] == pytest.approx(70.0)
    # label isolation: the pallas series did not leak into the numpy merge
    assert series[("repro_x_lat_us", (("backend", "pallas"),))]["sum"] == 7.0
    assert series[("repro_x_total", (("backend", "numpy"),))]["value"] == 3


def test_registry_shared_series_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("repro_shared_total", op="get")
    b = reg.counter("repro_shared_total", op="get")
    assert a is b  # same (name, labels) -> same object
    with pytest.raises(TypeError):
        reg.gauge("repro_shared_total", op="get")


def test_prometheus_bucket_counts_sum_to_op_counter():
    reg = MetricsRegistry()
    h = reg.register(Histogram("repro_y_lat_us", labels={"backend": "numpy"}))
    c = reg.counter("repro_y_requests_total", backend="numpy")
    for v in (1.5, 3.0, 1e9):  # includes one overflow sample
        h.record(v)
        c.inc()
    text = reg.render_prometheus()
    assert "# TYPE repro_y_lat_us histogram" in text
    assert "# TYPE repro_y_requests_total counter" in text
    counter = re.search(r'repro_y_requests_total\{backend="numpy"\} (\d+)', text)
    inf = re.search(r'repro_y_lat_us_bucket\{backend="numpy",le="\+Inf"\} (\d+)', text)
    count = re.search(r'repro_y_lat_us_count\{backend="numpy"\} (\d+)', text)
    assert counter and inf and count
    # the acceptance invariant: bucket counts sum to the op counter
    assert int(inf.group(1)) == int(count.group(1)) == int(counter.group(1)) == 3
    # cumulative buckets are non-decreasing
    cums = [
        int(m.group(1))
        for m in re.finditer(r'repro_y_lat_us_bucket\{[^}]*\} (\d+)', text)
    ]
    assert cums == sorted(cums)


# --------------------------------------------------------------------- tracer
def test_span_is_noop_without_ambient_context():
    tr = Tracer(MetricsRegistry())
    tr.enable()
    with tr.span("x") as ctx:
        assert ctx is None
        assert tr.current() is None
    assert tr.trace_dump() == []


def test_nested_spans_chain_parentage():
    tr = Tracer(MetricsRegistry())
    tr.enable()
    with tr.span("outer", root=True):
        octx = tr.current()
        with tr.span("inner", batch=3):
            ictx = tr.current()
            assert ictx.trace_id == octx.trace_id
        assert tr.current() == octx  # inner restored the ambient context
    assert tr.current() is None
    (trace,) = tr.trace_dump()
    spans = {s["name"]: s for s in trace["spans"]}
    assert trace["root"] == "outer"
    assert spans["outer"]["parent_id"] == 0
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["inner"]["annotations"] == {"batch": 3}
    _assert_parentage(trace)


def test_record_child_books_queue_hops():
    tr = Tracer(MetricsRegistry())
    tr.enable()
    root, _ = tr.new_context(None, inherit=False)
    tr.record("root", root, 0, 0, 1_000_000_000)
    tr.record_child("queue.wait", root, 100_000_000, 300_000_000, batch=7)
    (trace,) = tr.trace_dump()
    assert trace["trace_id"] == root.trace_id
    (qspan,) = [s for s in trace["spans"] if s["name"] == "queue.wait"]
    assert qspan["parent_id"] == root.span_id
    assert qspan["annotations"] == {"batch": 7}
    assert qspan["start_us"] == 100_000.0
    assert qspan["duration_us"] == 200_000.0


def test_trace_dump_slowest_first_and_ring_bounded():
    tr = Tracer(MetricsRegistry())
    tr.enable()
    assert tr.capacity == 4096  # the slow-request log's default
    tr.enable(capacity=8)
    assert tr.capacity == 8
    for i in range(12):
        ctx, pid = tr.new_context(None, inherit=False)
        tr.record(f"r{i}", ctx, pid, 0, (i + 1) * 1_000_000)
    dump = tr.trace_dump(4)
    assert [t["root"] for t in dump] == ["r11", "r10", "r9", "r8"]
    # the ring dropped the oldest spans: only 8 traces remain in total
    assert len(tr.trace_dump(100)) == 8
    # the slow-request log's format
    assert set(dump[0]) == {"trace_id", "duration_us", "root", "n_spans",
                            "spans"}
    (span,) = dump[0]["spans"]
    assert span == {"name": "r11", "trace_id": dump[0]["trace_id"],
                    "span_id": span["span_id"], "parent_id": 0,
                    "start_us": 0.0, "duration_us": 12_000.0,
                    "annotations": {}}
    assert dump[0]["duration_us"] == 12_000.0 and dump[0]["n_spans"] == 1


def test_off_tracer_allocates_nothing_and_mints_no_root():
    tr = Tracer(MetricsRegistry())
    ctx = TraceContext(new_trace_id(), 5)

    def sites(n):
        for _ in range(n):
            with tr.span("store.decode", batch=3):
                pass
            with tr.span("client.get", root=True):
                pass
            tr.record_child("service.coalesce", ctx, 1, 2)
            tr.current()

    sites(100)  # first-call caches
    gc.disable()
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        tracked = gc.get_count()[0]
        sites(10_000)
        tracked = gc.get_count()[0] - tracked
        current, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
        gc.enable()
    assert tracked == 0  # no object the collector tracks
    assert current - base <= 0
    assert peak - base < 1024  # the loop's own ints, nothing per span
    assert tr.trace_dump() == [] and tr.current() is None


def test_enabled_span_records_no_tracked_object():
    tr = Tracer(MetricsRegistry())
    tr.enable()

    def nested(n):
        for _ in range(n):
            with tr.span("outer", root=True):
                with tr.span("inner", batch=2):
                    pass

    nested(10)
    gc.disable()
    try:
        tracked = gc.get_count()[0]
        nested(2_000)
        tracked = gc.get_count()[0] - tracked
    finally:
        gc.enable()
    assert tracked == 0
    assert len(tr.trace_dump(10_000)) == min(2_010, tr.capacity // 2)


class _FakeClock:
    """``perf_counter_ns`` that returns the times a test sets."""

    def __init__(self):
        self.now = 0

    def perf_counter_ns(self):
        return self.now


def test_self_time_of_nested_spans_is_exact(monkeypatch):
    from repro.obs import trace as trace_mod

    clock = _FakeClock()
    monkeypatch.setattr(trace_mod, "time", clock)
    reg = MetricsRegistry()
    tr = Tracer(reg)
    tr.enable()

    def at(t, name=None, root=False):
        clock.now = t
        return tr.span(name, root=root) if name else None

    # outer [0, 100): a [10, 30), b [40, 70) holding c [45, 50)
    with at(0, "outer", root=True):
        with at(10, "a"):
            at(30)
        with at(40, "b"):
            with at(45, "c"):
                at(50)
            at(70)
        at(100)
    series = {(m["name"], m["labels"]["span"]): m["value"]
              for m in reg.snapshot()["metrics"]}
    own = {n: series[("repro_span_self_seconds_total", n)] * 1e9
           for n in ("outer", "a", "b", "c")}
    assert own == pytest.approx({"outer": 50, "a": 20, "b": 25, "c": 5})
    assert series[("repro_span_seconds_total", "outer")] * 1e9 == \
        pytest.approx(100)
    assert series[("repro_span_total", "b")] == 1
    # a second thread's spans add to the same counters
    def other_thread():
        with tr.span("c", root=True):
            pass

    t = threading.Thread(target=other_thread)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    counts = {m["labels"]["span"]: m["value"]
              for m in reg.snapshot()["metrics"]
              if m["name"] == "repro_span_total"}
    assert counts == {"outer": 1, "a": 1, "b": 1, "c": 2}


def test_enabled_spans_are_profiler_annotations_and_another_process_maps(
        tmp_path):
    """On a CPU profiler trace, every enabled span is an annotation of its
    name, the buffered spans map onto them, and a span timed in another
    process lands, by the same offset, inside the annotation that was open
    across it, to within 1 ms."""
    jax = pytest.importorskip("jax")
    import repro.kernels.ops  # noqa: F401  (installs the annotation hook)
    from repro.obs.trace import clock_offset

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    TRACER.enable()
    TRACER.clear()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for k in range(6):
            with TRACER.span("server.multiget", root=True):
                with TRACER.span("store.decode"):
                    time.sleep(0.001 * (k % 3 + 1))
        with TRACER.span("server.wait", root=True):
            out = subprocess.run(
                [sys.executable, "-c",
                 "import time; t0 = time.perf_counter_ns(); time.sleep(0.05);"
                 " print(t0, time.perf_counter_ns())"],
                capture_output=True, text=True, timeout=60).stdout
    finally:
        jax.profiler.stop_trace()
        TRACER.disable()
    c0, c1 = map(int, out.split())
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = [(ev.name, int(ev.start_ns), int(ev.end_ns))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name in ("server.multiget", "store.decode",
                             "server.wait")]
    names = [e[0] for e in events]
    assert names.count("server.multiget") == 6
    assert names.count("store.decode") == 6
    spans = [(s[0], s[4]) for s in TRACER.spans()]
    offset, residuals = clock_offset(
        spans, [(name, start) for name, start, _ in events])
    assert len(residuals) == 13
    assert residuals[-1] < 1_000_000
    (wait,) = [e for e in events if e[0] == "server.wait"]
    assert wait[1] - 1_000_000 <= c0 + offset
    assert c1 + offset <= wait[2] + 1_000_000


# ------------------------------------------------------------------- protocol
def test_trace_ctx_pack_roundtrip():
    ctx = TraceContext(new_trace_id(), 1234567890123)
    assert P.unpack_trace(P.pack_trace(ctx)) == ctx


def test_frame_trace_header_roundtrip_and_v1_compat():
    ctx = TraceContext(new_trace_id(), 42)
    payload = b"hello"
    traced = P.encode_frame(P.OP_MULTIGET, payload, trace=ctx)
    kind, got, trace, used = P.decode_frame_ex(traced + b"trailing")
    assert (kind, got, trace, used) == (P.OP_MULTIGET, payload, ctx, len(traced))
    # plain v1 frame -> no trace, and it is byte-identical to pre-trace frames
    plain = P.encode_frame(P.OP_MULTIGET, payload)
    kind, got, trace, used = P.decode_frame_ex(plain)
    assert (kind, got, trace, used) == (P.OP_MULTIGET, payload, None, len(plain))
    # the old-signature decoder sees the same payload with the trace stripped
    kind, got, used = P.decode_frame(traced)
    assert (kind, got, used) == (P.OP_MULTIGET, payload, len(traced))


def test_trace_header_over_socket():
    ctx = TraceContext(new_trace_id(), 99)
    a, b = socket.socketpair()
    try:
        P.send_frame(a, P.OP_PING, b"x", trace=ctx)
        P.send_frame(a, P.OP_PING, b"y")
        assert P.recv_frame_ex(b) == (P.OP_PING, b"x", ctx)
        # an old-API reader consumes the traced frame without seeing it
        P.send_frame(a, P.OP_PING, b"z", trace=ctx)
        assert P.recv_frame(b) == (P.OP_PING, b"y")
        assert P.recv_frame(b) == (P.OP_PING, b"z")
    finally:
        a.close()
        b.close()


# --------------------------------------------------------------------- compat
def test_old_client_v1_frames_against_new_server(server):
    """A pre-trace client speaks plain v1 frames: ping still echoes
    (non-probe payloads), multiget still answers."""
    sock = socket.create_connection(server.address, timeout=5)
    try:
        P.send_frame(sock, P.OP_PING, b"legacy")
        assert P.recv_frame(sock) == (P.ST_OK, b"legacy")
        P.send_frame(sock, P.OP_MULTIGET, P.pack_ids([0, 1]))
        status, resp = P.recv_frame(sock)
        assert status == P.ST_OK
        assert len(P.unpack_bytes_list(resp)) == 2
    finally:
        sock.close()


def test_new_client_probes_and_falls_back_to_v1(traced):
    """Against a server that echoes the caps probe (= an old server), a
    traced client must keep every wire frame at v1 — no trace header."""
    listener = socket.create_server(("127.0.0.1", 0))
    received = []

    def legacy_server():
        conn, _ = listener.accept()
        with conn:
            while True:
                got = P.recv_frame_ex(conn)
                if got is None:
                    return
                kind, payload, trace = got
                received.append((kind, payload, trace))
                if kind == P.OP_PING:
                    P.send_frame(conn, P.ST_OK, payload)  # verbatim echo
                else:
                    P.send_frame(conn, P.ST_OK, P.pack_bytes_list([b"a", b"b"]))

    t = threading.Thread(target=legacy_server, daemon=True)
    t.start()
    client = RemoteShardClient(
        listener.getsockname(), pool_size=1, reconnect_attempts=0
    )
    prev = TRACER.activate(TraceContext(new_trace_id(), 1))
    try:
        out = client.multiget([0, 1])
    finally:
        TRACER.restore(prev)
        client.close()
        listener.close()
    assert out == [b"a", b"b"]
    assert client._traced is False
    # the probe went out first, and nothing ever carried a trace header
    assert received[0][:2] == (P.OP_PING, P.CAPS_PROBE)
    assert all(trace is None for _, _, trace in received)


def test_caps_probe_against_new_server(server):
    client = RemoteShardClient(server.address)
    try:
        assert client.ping(b"abc") == b"abc"  # normal pings still echo
        assert client._probe_caps() is True
        assert client._traced is True
    finally:
        client.close()


# ----------------------------------------------------------------- end-to-end
def test_untraced_client_sends_no_trace_header(tcp_cluster, monkeypatch):
    """Tracing off (the default): the client mints no root, never probes
    for trace support, and no frame carries a trace header."""
    sent = []
    send = P.send_frame

    def spy(sock, kind, payload=b"", trace=None):
        sent.append(trace)
        return send(sock, kind, payload, trace=trace)

    monkeypatch.setattr(P, "send_frame", spy)
    assert not TRACER.on
    TRACER.clear()
    url = "tcp://" + ",".join(f"{h}:{p}" for h, p in
                              (s.address for s in tcp_cluster))
    client = connect(url)
    try:
        assert len(client.multiget([0, 1, 2, 3])) == 4
        assert len(client.get_async(5).result(30)) > 0
    finally:
        client.close()
    assert sent and all(t is None for t in sent)
    assert all(c._traced is None for c in client.backend.clients)
    assert TRACER.spans() == []


def test_trace_spans_local_shard_multiget(sharded_dir, traced):
    client = connect(f"shard://{sharded_dir}")
    try:
        out = client.multiget([0, 1, 2, 5])
        assert len(out) == 4
    finally:
        client.close()
    trace = next(
        t for t in TRACER.trace_dump(8) if t["root"] == "client.multiget"
    )
    names = {s["name"] for s in trace["spans"]}
    assert {"client.multiget", "store.decode"} <= names
    _assert_parentage(trace)


def test_tcp_multiget_trace_has_full_span_chain(tcp_cluster, traced):
    url = "tcp://" + ",".join(f"{h}:{p}" for h, p in (s.address for s in tcp_cluster))
    client = connect(url)
    try:
        out = client.multiget([0, 1, 2, 3])
        assert len(out) == 4
    finally:
        client.close()
    trace = next(
        t for t in TRACER.trace_dump(8) if t["root"] == "client.multiget"
    )
    names = {s["name"] for s in trace["spans"]}
    # the chain: client -> socket -> server -> queue wait -> batch -> store
    assert {"client.multiget", "rpc.multiget", "server.multiget",
            "service.coalesce", "service.batch", "store.multiget",
            "store.decode", "store.tokens", "store.cache_put"} <= names
    assert trace["n_spans"] >= 9
    _assert_parentage(trace)
    decode = next(s for s in trace["spans"] if s["name"] == "store.decode")
    assert decode["annotations"]["batch"] >= 1
    coalesce = next(s for s in trace["spans"] if s["name"] == "service.coalesce")
    assert coalesce["annotations"]["batch"] >= 1


def test_stats_metrics_extension_and_trace_dump_rpc(tcp_cluster, traced):
    client = RemoteShardClient(tcp_cluster[0].address)
    try:
        plain = client.stats()
        assert "metrics" not in plain  # the extension is opt-in
        ctx, _ = TRACER.new_context(None, inherit=False)
        prev = TRACER.activate(ctx)
        try:
            client.multiget([0, 1])
        finally:
            TRACER.restore(prev)
        stats = client.stats(metrics=True)
        names = {m["name"] for m in stats["metrics"]["metrics"]}
        assert "repro_rpc_requests_total" in names
        assert "repro_store_multiget_latency_us" in names
        assert "repro_service_request_latency_us" in names
        # the span counters ride the same extension
        spans = {m["labels"]["span"] for m in stats["metrics"]["metrics"]
                 if m["name"] == "repro_span_self_seconds_total"}
        assert {"server.multiget", "service.batch", "store.multiget"} <= spans
        # the server's slow-request log is reachable over RPC and holds the
        # traced multiget with its server-side spans
        dump = client.trace_dump(16)
        trace = next(t for t in dump if t["trace_id"] == ctx.trace_id)
        names = {s["name"] for s in trace["spans"]}
        assert {"server.multiget", "service.coalesce", "store.decode"} <= names
    finally:
        client.close()


# ----------------------------------------------------------------------- http
def test_metrics_http_server_endpoints():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    tr.enable()
    h = reg.register(Histogram("repro_z_lat_us", labels={"backend": "numpy"}))
    c = reg.counter("repro_z_requests_total")
    for v in (2.0, 8.0, 40.0):
        h.record(v)
        c.inc()
    with tr.span("req", root=True):
        pass
    srv = start_metrics_server(port=0, registry=reg, tracer=tr)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        count = re.search(r'repro_z_lat_us_count\{backend="numpy"\} (\d+)', text)
        inf = re.search(r'repro_z_lat_us_bucket\{backend="numpy",le="\+Inf"\} (\d+)', text)
        assert count and int(count.group(1)) == 3
        assert inf and int(inf.group(1)) == 3
        assert re.search(r"repro_z_requests_total 3\b", text)
        assert re.search(r'repro_span_total\{span="req"\} 1\b', text)
        traces = json.loads(
            urllib.request.urlopen(base + "/traces?n=4").read().decode()
        )
        assert traces and traces[0]["root"] == "req"
        assert urllib.request.urlopen(base + "/healthz").read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")
    finally:
        srv.close()
