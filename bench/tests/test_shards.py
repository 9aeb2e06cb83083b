"""Tests of the harness's shards, on the CPU at tiny sizes.

A configuration's ``shards`` decides how ``bench.run`` builds and serves
the store: one store directory and one port, as before, for one shard;
``shards`` segment-aligned directories sharing one dictionary, each served
by its own ``ShardServer`` in the one server process on a device of its
own, behind the program's ``tcp://`` router, for more. The whole runs give
the server child two CPU devices (``--xla_force_host_platform_device_count``)
so that two shards each find one. Also: the counters and device memory
summed over every server and device, the fan-out clock and its reader, and
the trace reduction over one device and over four.
"""

from __future__ import annotations

import json
import math
import os
import time

import pytest
from bench.tests.test_bench import (TINY_BENCH, TINY_CONFIG, TINY_TRAFFIC,
                                    _write, tiny_root)  # noqa: F401

from bench import layout, run
from bench import trace as btrace
from bench.fanout import FanoutClock
from bench.serve import device_memory

SEED = 2**31 + 23


def _shard_root(root: str, shards: int) -> None:
    """Add the cell ``tiny2.get``: the tiny get mix over ``shards``
    shards."""
    bench = json.loads(json.dumps(TINY_BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tiny2",
                             "file": "bench/configs/tiny2.json"})
    bench["workloads"].append({"name": "tiny2.get", "config": "tiny2",
                               "traffic": "tiny_get", "chips": 1,
                               "why": "open-loop gets over shards"})
    for m in bench["end_to_end"]:
        if "tiny.get" in m.get("workloads", []):
            m["workloads"].append("tiny2.get")
    bench["per_layer"] += [
        {"name": name, "unit": unit, "better": "lower",
         "source": "host_clock", "layer": "router fan-out",
         "moves": "read_p50_ms", "workloads": ["tiny2.get"]}
        for name, unit in (("straggler_ms.tiny2", "ms"),
                           ("ids_per_rpc.tiny2", "ids"),
                           ("busy_skew.tiny2", "ratio"))]
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    _write(os.path.join(root, "bench", "configs", "tiny2.json"),
           {**TINY_CONFIG, "name": "tiny2", "shards": shards,
            "chips": shards})


@pytest.fixture
def own_work(monkeypatch, tmp_path):
    """Whole runs here build in a work directory of their own, apart from
    other test files' runs."""
    monkeypatch.setattr(run, "WORK", str(tmp_path / "work"))


@pytest.fixture
def two_devices(monkeypatch):
    """The server child sees two CPU devices."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")


def _run(capsys, root, workload, fault=None, trace=0):
    argv = ["--workload", workload, "--seed", str(SEED), "--seconds", "0.5",
            "--trace", str(trace)]
    if fault:
        argv += ["--fault", fault]
    rc = run.main(argv, root=root, platform=None)
    out, err = capsys.readouterr()
    return rc, out, err


def _shard_lines(out: str) -> list[str]:
    return [ln for ln in out.splitlines() if ln.startswith("shard ")]


# ------------------------------------------------------------------- build
def test_one_shard_builds_one_store_directory(tmp_path):
    from repro.store import CompressedStringStore

    path = str(tmp_path / "store")
    built = run.build_store(TINY_CONFIG, SEED, path)
    assert built["paths"] == [path]
    assert not os.path.exists(os.path.join(path, "shards.json"))
    assert not any(n.startswith("shard-") for n in os.listdir(path))
    store = CompressedStringStore.open(path)
    assert store.n_strings == len(built["strings"])
    assert store.multiget([0, len(built["strings"]) - 1]) == [
        built["strings"][0], built["strings"][-1]]


def test_shards_build_segment_aligned_directories_sharing_one_dictionary(
        tmp_path):
    from repro.distributed.shard_store import open_shard

    path = str(tmp_path / "store")
    built = run.build_store({**TINY_CONFIG, "shards": 2}, SEED, path)
    assert built["paths"] == [os.path.join(path, "shard-0000"),
                              os.path.join(path, "shard-0001")]
    with open(os.path.join(path, "shards.json")) as f:
        manifest = json.load(f)
    sps = TINY_CONFIG["store"]["strings_per_segment"]
    (lo0, hi0), (lo1, hi1) = manifest["bounds"]
    assert lo0 == 0 and hi0 == lo1 and hi1 == len(built["strings"])
    assert hi0 % sps == 0 and hi0 > 0
    assert os.path.exists(os.path.join(path, "dictionary.rpa"))
    for k, (lo, hi) in enumerate(manifest["bounds"]):
        shard = open_shard(path, k)
        assert shard.n_strings == hi - lo
        assert shard.multiget([0, hi - lo - 1]) == [built["strings"][lo],
                                                    built["strings"][hi - 1]]


# -------------------------------------------------------------- whole runs
def test_one_shard_run_serves_one_port(tiny_root, own_work, capsys):
    rc, out, _ = _run(capsys, tiny_root, "tiny.get")
    assert rc == 0
    assert len(_shard_lines(out)) == 1
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True


@pytest.mark.parametrize("fault,correct", [
    (None, True),
    ("lossy8", False),     # the control
    ("bypass", False),     # right answers, none from the kernel
    ("oneshard", False),   # every shard answered from the first one's data
    ("flip", False),       # a byte altered where the decode produces it
    ("half", False),       # half of each decode batch left out
])
def test_two_shard_run_decides_correct(tiny_root, own_work, two_devices,
                                       capsys, fault, correct):
    _shard_root(tiny_root, 2)
    rc, out, err = _run(capsys, tiny_root, "tiny2.get", fault)
    assert rc == 0, err
    shards = _shard_lines(out)
    assert len(shards) == 2
    assert "'id': 0" in shards[0] and "'id': 1" in shards[1]
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is correct
    assert result["failed"] == 0
    checks = result["checks"]
    assert (checks["kernel_strings"]["value"] >= 1) is (
        fault not in ("bypass", "oneshard"))
    if fault in ("lossy8", "oneshard", "flip", "half"):
        assert checks["wrong_strings"]["value"] > 0
    assert set(result["metrics"]) == {"read_p50_ms", "setup_s",
                                      "bytes_per_raw_byte"}


def test_two_shard_traced_run_times_the_fan_out(tiny_root, own_work,
                                                two_devices, capsys):
    _shard_root(tiny_root, 2)
    # the run looks up its device's peaks: give this test's CPU a row
    peaks_path = os.path.join(tiny_root, "bench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    _write(peaks_path, {**peaks, "cpu": peaks["TPU v5 lite"]})
    rc, out, err = _run(capsys, tiny_root, "tiny2.get", trace=1)
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = result["metrics"]
    assert metrics["straggler_ms.tiny2"]["value"] >= 0
    assert metrics["ids_per_rpc.tiny2"]["value"] > 0


def test_run_refuses_a_config_served_as_fewer_shards(tiny_root, own_work,
                                                     monkeypatch, capsys):
    # the tiny corpus has 5 segments, so 6 shards are saved as 5
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=6")
    _shard_root(tiny_root, 6)
    rc, out, err = _run(capsys, tiny_root, "tiny2.get")
    assert rc == 1
    assert "declares 6 shards, the run serves 5" in err
    assert not out.strip().splitlines()[-1].startswith("{")


def test_shards_on_one_device_are_refused(tiny_root, own_work, capsys):
    _shard_root(tiny_root, 2)
    rc, out, err = _run(capsys, tiny_root, "tiny2.get")
    assert rc == 1
    assert "each shard needs a device of its own" in err


# ------------------------------------------------- sums over servers, devices
class _Shard:
    def __init__(self, decoded, multigets, memory):
        self.store = {"lookups": 10 * decoded, "decoded_strings": decoded,
                      "decoded_bytes": 80 * decoded, "batches": 2,
                      "padded_rows": 512, "jit_shapes": ["a", "b"],
                      "cache": {"hits": 3, "misses": decoded},
                      "memory_bytes": memory}
        self.ops = {"multiget": multigets}

    def stats(self):
        return {"store": self.store, "ops": self.ops}


class _Client:
    def __init__(self, shards):
        self.backend = type("Router", (), {"clients": shards})()


def test_counters_sum_every_server():
    client = _Client([_Shard(5, 2, 1000), _Shard(7, 3, 2000)])
    got = run.counters(client)
    assert got == {"lookups": 120, "decoded_strings": 12,
                   "decoded_bytes": 960, "batches": 4, "padded_rows": 1024,
                   "jit_shapes": 4, "cache_hits": 6, "cache_misses": 12,
                   "rpc_multiget": 5, "rpc_get": 0, "memory_bytes": 3000}
    assert run.shard_decoded(client) == [5, 7]
    one = run.counters(_Client([_Shard(5, 2, 1000)]))
    assert one["decoded_strings"] == 5 and one["memory_bytes"] == 1000


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_device_memory_sums_in_use_and_takes_the_fullest_peak():
    a = _Device({"bytes_in_use": 100, "peak_bytes_in_use": 400})
    b = _Device({"bytes_in_use": 50, "peak_bytes_in_use": 900})
    # a device that holds two shards counts once
    assert device_memory([a, b, a]) == {"bytes_in_use": 150,
                                        "peak_bytes_in_use": 900}
    assert device_memory([a]) == {"bytes_in_use": 100,
                                  "peak_bytes_in_use": 400}
    assert device_memory([a, _Device(None)]) == {"bytes_in_use": None,
                                                 "peak_bytes_in_use": None}


# ---------------------------------------------------------------- fan-out
def test_fanout_clock_keeps_slowest_and_fastest_shard_call():
    from repro.distributed.shard_store import ShardRouter

    class Router(ShardRouter):
        def _shard_multiget(self, k, local_ids, read_preference=None):
            time.sleep(0.002 + 0.02 * k)
            return [b"%d:%d" % (k, i) for i in local_ids]

    router = Router([(0, 10), (10, 20), (20, 30)])
    clock = FanoutClock([router])
    assert router.multiget([25, 1, 12]) == [b"2:5", b"0:1", b"1:2"]
    assert router.multiget([3, 4]) == [b"0:3", b"0:4"]   # one shard
    assert router.get(15) == b"1:5"
    fan = clock.summary()
    assert len(fan["slowest_s"]) == len(fan["fastest_s"]) == 1
    assert fan["slowest_s"][0] >= 0.04 > fan["fastest_s"][0] >= 0.002
    read = layout.metric_reader("straggler_ms.sharded4")
    assert read({"fanout": fan}) == pytest.approx(
        1e3 * (fan["slowest_s"][0] - fan["fastest_s"][0]))
    assert read({"fanout": {"slowest_s": [], "fastest_s": []}}) is None
    assert read({"fanout": None}) is None
    assert read({"fanout": {"slowest_s": [0.03, 0.01],
                            "fastest_s": [0.01, 0.01]}}) == pytest.approx(10)


# ------------------------------------------------------------------ trace
def test_busy_skew_reads_the_busiest_chip_over_the_mean():
    read = layout.metric_reader("busy_skew.sharded4")
    assert read({"trace": {"busy_s_per_device": [1.0, 1.0, 1.0, 1.0]}}) == 1
    assert read({"trace": {"busy_s_per_device": [2.0, 1.0, 0.5, 0.5]}}
                ) == pytest.approx(2.0)
    assert read({"trace": {"busy_s_per_device": [0.3]}}) is None
    assert read({"trace": {"busy_s_per_device": [0.0, 0.0]}}) is None
    assert read({"trace": None}) is None


def test_four_device_trace_divides_the_roofline_by_chip_seconds():
    host = [("bench.kernel.decode_batch", 0.0, 4.0)]
    device = [[("fusion", 1.0, 1.0 + 0.5 * (k + 1), True),
               ("copy-start", 9.0, 9.5, True)] for k in range(4)]
    out = btrace.reduce_events(device, host, (0.0, 10.0))
    assert out["compute_s_per_device"] == pytest.approx([0.5, 1.0, 1.5, 2.0])
    assert out["compute_s"] == pytest.approx(1.25)
    assert out["busy_s_per_device"] == pytest.approx([1.0, 1.5, 2.0, 2.5])
    gaps = dict(out["idle_gaps"])
    # every chip's idle time charged on its own, averaged over the chips
    assert sum(gaps.values()) == pytest.approx(10.0 - out["busy_s"])
    assert gaps["idle:kernel.decode_batch"] == pytest.approx(4.0 - 1.25)
    ctx = {"counters": {"real_tokens": 10_000, "decoded_bytes": 20_000},
           "peaks": {"hbm_bytes_per_s": 819e9}, "trace": out}
    nbytes = 10_000 * 18 + 20_000
    assert layout.metric_reader("decode_roofline.sharded4")(ctx) == (
        pytest.approx(100 * nbytes / (5.0 * 819e9)))
    assert layout.metric_reader("device_idle_share.sharded4")(ctx) == (
        pytest.approx(100 * (1 - 1.75 / 10)))
    assert layout.metric_reader("busy_skew.sharded4")(ctx) == (
        pytest.approx(2.5 / 1.75))
    assert not math.isnan(out["busy_s"])


def test_one_device_trace_reads_as_before():
    device = [[("fusion", 1.0, 2.0, True), ("dma", 5.5, 6.0, False)]]
    host = [("bench.rpc", 0.0, 10.0)]
    out = btrace.reduce_events(device, host, (0.0, 10.0))
    assert out["busy_s"] == out["busy_s_per_device"][0] == pytest.approx(1.5)
    assert out["compute_s"] == out["compute_s_per_device"][0] == 1.0
    assert dict(out["idle_gaps"]) == pytest.approx({"idle:rpc": 8.5})
    none = btrace.reduce_events([], host, (0.0, 10.0))
    assert none["busy_s"] == 0 and dict(none["idle_gaps"]) == {
        "idle:rpc": 10.0}
