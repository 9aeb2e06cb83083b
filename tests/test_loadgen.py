"""repro.loadgen acceptance: deterministic schedules, drivers over real
backends, the server-histogram SLO gate (both verdicts), hedged-read
cancellation (proved by server-side op counters), replica autodiscovery
from the manifest, and the Prometheus scrape round-trip the open-loop
collector relies on."""

import os
import subprocess
import sys

import pytest

from repro.client import connect, format_tcp_url
from repro.data.synth import load_dataset
from repro.distributed import save_sharded
from repro.distributed.shard_store import manifest_replicas, record_replicas
from repro.loadgen import (
    SLO,
    WorkloadSpec,
    build_report,
    build_schedule,
    fraction_under,
    run_workload,
    snapshot_server_states,
)
from repro.net import ShardServer
from repro.obs import (
    Histogram,
    MetricsRegistry,
    hist_state_from_rows,
    parse_prometheus,
    render_prometheus,
)
from repro.store import CompressedStringStore

SAMPLE = 1 << 18


@pytest.fixture(scope="module")
def titles():
    return load_dataset("book_titles", SAMPLE)


@pytest.fixture(scope="module")
def corpus(titles, tmp_path_factory):
    """One flat store dir + one 2-shard sharded dir."""
    store = CompressedStringStore.build(
        titles, sample_bytes=SAMPLE, strings_per_segment=256
    )
    base = tmp_path_factory.mktemp("loadgen")
    flat = str(base / "flat")
    store.save(flat)
    sharded = str(base / "shards")
    save_sharded(store, sharded, 2)
    return {"flat": flat, "sharded": sharded}


# ------------------------------------------------------------------ schedule
class TestSchedule:
    def test_same_seed_same_spec_identical_schedule(self):
        spec = WorkloadSpec(
            mix={"get": 0.5, "multiget": 0.3, "scan": 0.2},
            loop="open",
            rate=500.0,
            seed=42,
        )
        a = build_schedule(spec, 10_000, 3000)
        b = build_schedule(spec, 10_000, 3000)
        assert a == b
        assert len(a) == 3000

    def test_different_seed_different_schedule(self):
        base = dict(mix={"get": 1.0}, seed=1)
        a = build_schedule(WorkloadSpec(**base), 10_000, 500)
        b = build_schedule(WorkloadSpec(**{**base, "seed": 2}), 10_000, 500)
        assert a != b

    def test_shapes_and_arrivals(self):
        spec = WorkloadSpec(
            mix={"get": 0.6, "multiget": 0.4},
            multiget_fanout=8,
            loop="open",
            rate=1000.0,
            seed=0,
        )
        sched = build_schedule(spec, 5000, 2000)
        kinds = {op.kind for op in sched}
        assert kinds == {"get", "multiget"}
        arrivals = [op.at_s for op in sched]
        assert arrivals == sorted(arrivals)  # Poisson schedule is cumulative
        for op in sched:
            if op.kind == "multiget":
                assert len(op.ids) == 8
            assert all(0 <= i < 5000 for i in op.ids)

    def test_closed_loop_arrivals_all_zero(self):
        sched = build_schedule(WorkloadSpec(mix={"get": 1.0}), 100, 64)
        assert all(op.at_s == 0.0 for op in sched)

    def test_spec_json_roundtrip(self):
        spec = WorkloadSpec(
            mix={"get": 1.0},
            loop="open",
            rate=250.0,
            seed=9,
            slo=SLO(p99_ms=5.0, min_goodput=0.9),
        )
        again = WorkloadSpec.from_json(spec.to_json())
        assert again == spec
        assert build_schedule(again, 1000, 100) == build_schedule(
            spec, 1000, 100
        )

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            WorkloadSpec(mix={"teleport": 1.0})
        with pytest.raises(ValueError):
            WorkloadSpec(loop="möbius")
        with pytest.raises(ValueError):
            WorkloadSpec(mix={"get": 0.0})


# ------------------------------------------------------------------- drivers
class TestDrivers:
    def test_closed_loop_over_sharded_backend(self, corpus, titles):
        spec = WorkloadSpec(
            mix={"get": 0.7, "multiget": 0.3}, concurrency=16, seed=3
        )
        with connect(f"shard://{corpus['sharded']}") as client:
            result = run_workload(client, spec, duration_s=0.5)
        assert result.loop == "closed"
        assert result.ops_ok > 0
        assert result.ops_failed == 0
        assert result.per_kind.get("get", 0) > 0
        assert sum(result.latency_state["counts"]) == result.ops_ok
        assert result.bytes_read > 0

    def test_open_loop_paces_to_rate(self, corpus):
        spec = WorkloadSpec(
            mix={"get": 1.0}, loop="open", rate=200.0, seed=5
        )
        with connect(f"shard://{corpus['sharded']}") as client:
            result = run_workload(client, spec, duration_s=1.0)
        assert result.loop == "open"
        assert result.ops_ok > 0
        # paced, not saturating: issue count tracks rate x duration, far
        # below what a closed loop would push through in a second
        assert result.ops_issued <= 2 * 200

    def test_writes_in_mix(self, corpus, tmp_path):
        spec = WorkloadSpec(
            mix={"get": 0.5, "append": 0.25, "extend": 0.25},
            concurrency=4,
            extend_batch=8,
            seed=11,
        )
        with connect(f"shard://{corpus['sharded']}", writable=True) as client:
            n0 = client.n_strings
            result = run_workload(client, spec, duration_s=0.3)
            assert result.ops_failed == 0
            assert client.n_strings > n0


# ------------------------------------------------------------------ SLO gate
class TestSLOGate:
    def _run(self, corpus, slo: SLO):
        spec = WorkloadSpec(mix={"get": 1.0}, concurrency=8, seed=2, slo=slo)
        # file:// runs the local micro-batching service, so the *server*
        # histogram (repro_service_request_latency_us) lives in-process
        with connect(f"file://{corpus['flat']}") as client:
            before = snapshot_server_states(client)
            result = run_workload(client, spec, duration_s=0.3)
            after = snapshot_server_states(client)
            return build_report(spec, result, before, after, client=client)

    def test_gate_passes_under_generous_slo(self, corpus):
        report = self._run(corpus, SLO(p99_ms=10_000.0))
        assert report["passed"] is True
        assert report["violations"] == []
        assert report["server_latency"]["count"] > 0
        assert report["goodput"]["fraction_under_slo"] == 1.0

    def test_gate_fails_under_impossible_slo(self, corpus):
        report = self._run(
            corpus, SLO(p99_ms=0.0001, min_goodput=1.0)
        )
        assert report["passed"] is False
        names = {v["slo"] for v in report["violations"]}
        assert "p99_ms" in names
        assert "min_goodput" in names
        for v in report["violations"]:
            assert "trace_excerpt" in v  # attached even when empty

    def test_fraction_under(self):
        state = {"bounds": [10.0, 100.0], "counts": [5, 5, 0], "sum": 300.0}
        assert fraction_under(state, 10.0) == 0.5
        assert fraction_under(state, 1000.0) == 1.0
        assert fraction_under(state, 5.0) == pytest.approx(0.25)
        assert fraction_under(None, 10.0) == 0.0


# --------------------------------------------------------------- hedged reads
class TestHedgedReads:
    @pytest.fixture()
    def replicated(self, titles, tmp_path):
        """2-shard in-thread cluster + a read-only replica on shard 0."""
        store = CompressedStringStore.build(
            titles[:1500], sample_bytes=SAMPLE, strings_per_segment=256
        )
        d = str(tmp_path / "shards")
        save_sharded(store, d, 2)
        servers = [
            ShardServer.from_dir(os.path.join(d, f"shard-{k:04d}")).start()
            for k in range(2)
        ]
        replica = ShardServer.from_dir(
            os.path.join(d, "shard-0000"), read_only=True
        ).start()
        client = connect(format_tcp_url([s.address for s in servers]))
        client.register_replica(0, replica.address)
        yield client, servers, replica
        client.close()
        for s in [*servers, replica]:
            s.close()

    @staticmethod
    def _reads(server) -> int:
        return sum(
            server.op_counts.get(op, 0) for op in ("get", "multiget")
        )

    def test_unfired_hedge_is_cancelled(self, replicated):
        """Primary answers first -> the timer is cancelled and the replica
        never sees a single read (server-side op counters)."""
        client, _servers, replica = replicated
        r0 = self._reads(replica)
        for i in range(20):
            assert client.get_hedged(i, hedge_ms=2000.0) == client.get(i)
        assert self._reads(replica) == r0
        assert client.stats()["hedges"] == 0

    def test_fired_hedge_loser_cancelled(self, replicated):
        """hedge_ms=0 fires the second attempt on every read: both sides
        serve some traffic, every result is correct, and the op counters
        bound total server work at <= 2 per request — the losing attempt
        either completes or is cancelled, it is never retried/duplicated."""
        client, servers, replica = replicated
        n = 40
        p0 = self._reads(servers[0])
        r0 = self._reads(replica)
        expected = client.multiget(list(range(n)))
        base_stats = client.stats()
        for i in range(n):
            assert (
                client.get_hedged(i, hedge_ms=0.0, hedge_preference="replica")
                == expected[i]
            )
        stats = client.stats()
        assert stats["hedges"] - base_stats["hedges"] == n
        served_p = self._reads(servers[0]) - p0
        served_r = self._reads(replica) - r0
        # every request reached at least one server, no attempt duplicated
        # past the budget, and the hedge target actually saw traffic
        assert served_r >= 1
        assert n <= served_p + served_r <= 2 * n + len(expected)

    def test_hedge_budget_retries_failures(self, replicated):
        """budget > 1 also acts as a retry budget: an id out of range fails
        every attempt and surfaces the error (not a hang)."""
        client, _servers, _replica = replicated
        with pytest.raises(Exception):
            client.get_hedged(10**9, hedge_ms=0.0, budget=2, timeout=5.0)


# -------------------------------------------------------- replica discovery
class TestReplicaAutodiscovery:
    def test_connect_registers_manifest_replicas(self, titles, tmp_path):
        store = CompressedStringStore.build(
            titles[:1500], sample_bytes=SAMPLE, strings_per_segment=256
        )
        d = str(tmp_path / "shards")
        save_sharded(store, d, 2)
        servers = [
            ShardServer.from_dir(os.path.join(d, f"shard-{k:04d}")).start()
            for k in range(2)
        ]
        replica = ShardServer.from_dir(
            os.path.join(d, "shard-0001"), read_only=True
        ).start()
        # record one live replica and one dead address: discovery must
        # register the live one and shrug off the dead one
        record_replicas(d, {1: [replica.address, ("127.0.0.1", 1)]})
        assert manifest_replicas(d)[1][0] == replica.address
        client = None
        try:
            client = connect(
                format_tcp_url([s.address for s in servers]), dir_path=d
            )
            r0 = replica.op_counts.get("multiget", 0)
            # ids from shard 1's range — the shard the replica covers
            lo = client.backend.bounds[1][0]
            client.multiget([lo, lo + 1, lo + 2], read_preference="replica")
            assert replica.op_counts.get("multiget", 0) > r0
        finally:
            if client is not None:
                client.close()
            for s in [*servers, replica]:
                s.close()

    def test_auto_replicas_off_by_flag(self, titles, tmp_path):
        store = CompressedStringStore.build(
            titles[:800], sample_bytes=SAMPLE, strings_per_segment=256
        )
        d = str(tmp_path / "shards")
        save_sharded(store, d, 1)
        server = ShardServer.from_dir(os.path.join(d, "shard-0000")).start()
        replica = ShardServer.from_dir(
            os.path.join(d, "shard-0000"), read_only=True
        ).start()
        record_replicas(d, {0: [replica.address]})
        try:
            with connect(
                format_tcp_url([server.address]),
                dir_path=d,
                auto_replicas=False,
            ) as client:
                r0 = replica.op_counts.get("multiget", 0)
                client.multiget([1, 2], read_preference="any")
                client.multiget([1, 2], read_preference="any")
                assert replica.op_counts.get("multiget", 0) == r0
        finally:
            server.close()
            replica.close()


# ------------------------------------------------------------- get batching
class TestGetBatcher:
    def test_concurrent_gets_coalesce_into_multiget(self, corpus, titles):
        servers = [
            ShardServer.from_dir(
                os.path.join(corpus["sharded"], f"shard-{k:04d}")
            ).start()
            for k in range(2)
        ]
        try:
            with connect(
                format_tcp_url([s.address for s in servers])
            ) as client:
                gets_before = sum(
                    s.op_counts.get("get", 0) for s in servers
                )
                futs = [client.get_async(i) for i in range(200)]
                vals = [f.result(timeout=30) for f in futs]
                assert vals == titles[:200]
                stats = client.stats()
                assert stats["coalesced_gets"] > 0
                assert stats["get_batches"] < 200
                # point reads traveled as multiget RPCs, not per-get calls
                gets_after = sum(s.op_counts.get("get", 0) for s in servers)
                assert gets_after == gets_before
        finally:
            for s in servers:
                s.close()


# ------------------------------------------------------- scrape round-trip
class TestScrapeRoundTrip:
    def test_prometheus_text_rebuilds_exact_hist_state(self):
        reg = MetricsRegistry()
        hist = reg.register(Histogram("rt_latency_us", {"shard": "0"}))
        for v in (3.0, 42.0, 9001.0, 1e7):
            hist.record(v)
        reg.register(Histogram("rt_latency_us", {"shard": "1"})).record(5.0)
        rows = parse_prometheus(render_prometheus(reg))
        state = hist_state_from_rows(rows, "rt_latency_us", {"shard": "0"})
        assert state == hist.state()
        other = hist_state_from_rows(rows, "rt_latency_us", {"shard": "1"})
        assert sum(other["counts"]) == 1


def test_build_demo_corpus_leaves_jax_uninitialised(tmp_path):
    """The spawner builds and saves without a device, so a shard server it
    starts can own the chip."""
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = (
        "import sys\n"
        "from repro.loadgen.cluster import build_demo_corpus\n"
        f"n = build_demo_corpus({str(tmp_path)!r}, n_shards=2, target_mib=1)\n"
        "assert n > 0\n"
        "if 'jax' in sys.modules:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "print('ok')\n")
    env = {**os.environ, "PYTHONPATH": src}
    env.pop("REPRO_NO_JAX", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
