"""Tests of the chip benchmark's harness, on the CPU at tiny sizes.

They check the layout (every name in ``BENCHMARK.json`` finds its file, and
a new configuration, traffic mix or metric is found with no code edit), the
generator and schedule determinism, the exact latency, trace and roofline
arithmetic, and whole runs of a tiny cell: a sound run is correct, the
control (``lossy8``), a byte altered where the decode produces it
(``flip``), half of each decode batch left out (``half``), dropped
answers (``drop``) and right answers that the kernel never decoded
(``bypass``) are not, and a run with no TPU or no program prints no
result. No test describes or touches a TPU.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import datasets, drive, latency, layout, roofline, run, schedule
from bench import trace as btrace

ROOT = layout.ROOT

TINY_BENCH = {
    "command": ["python3", "-m", "bench.run"],
    "paths": ["bench"],
    "run_seconds": 1,
    "configs": [{"name": "tiny", "source": "https://arxiv.org/abs/2508.02280",
                 "file": "bench/configs/tiny.json", "reduced": ["raw_mib"],
                 "why": "a test-sized titles column"}],
    "workloads": [
        {"name": "tiny.gather", "config": "tiny", "traffic": "tiny_gather",
         "chips": 1, "why": "closed-loop multigets"},
        {"name": "tiny.get", "config": "tiny", "traffic": "tiny_get",
         "chips": 1, "why": "open-loop gets"}],
    "end_to_end": [
        {"name": "read_p90_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.gather"]},
        {"name": "read_p50_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.get"]},
        {"name": "strings_per_s", "unit": "strings/s", "better": "higher",
         "bound": 0.1, "source": "host_clock", "workloads": ["tiny.gather"]},
        {"name": "bytes_per_raw_byte", "unit": "B/B", "better": "lower",
         "bound": 0.01, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": [
        {"name": "cache_hit_share.gather", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "store cache",
         "moves": "strings_per_s", "workloads": ["tiny.gather"]},
        {"name": "ids_per_rpc.get", "unit": "ids", "better": "higher",
         "source": "program_counter", "layer": "RPC and service",
         "moves": "read_p50_ms", "workloads": ["tiny.get"]},
        {"name": "read_p90_ms.get", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "client",
         "moves": "read_p50_ms", "workloads": ["tiny.get"]}],
}
TINY_CONFIG = {
    "name": "tiny", "dataset": "book_titles",
    "dataset_shape": {"words": [3, 10]}, "raw_mib": 1,
    "codec": "onpair16", "chips": 1,
    "store": {"strings_per_segment": 4096, "cache_bytes": 65536,
              "batch_size": 32, "num_buckets": 4, "sample_bytes": 262144},
}
TINY_TRAFFIC = {
    "tiny_gather": {"loop": "closed", "clients": 2, "op": "multiget",
                    "fanout": 64, "requests_per_client": 4000,
                    "ids": {"dist": "uniform", "unique": True,
                            "sorted": True},
                    "warm": {"requests": 4, "fanout": 64}},
    "tiny_get": {"loop": "open", "clients": 1, "op": "get", "rate": 200,
                 "ids": {"dist": "zipf", "zipf_s": 0.99,
                         "scatter": 2654435761},
                 "warm": {"requests": 4, "fanout": 64}},
}


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path):
    """A benchmark root holding only the tiny cells' data files, the real
    metric readers and the real peaks table."""
    root = str(tmp_path)
    _write(os.path.join(root, "BENCHMARK.json"), TINY_BENCH)
    _write(os.path.join(root, "bench", "configs", "tiny.json"), TINY_CONFIG)
    for name, traffic in TINY_TRAFFIC.items():
        _write(os.path.join(root, "bench", "traffic", name + ".json"),
               traffic)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"))
    shutil.copy(os.path.join(ROOT, "bench", "peaks.json"),
                os.path.join(root, "bench", "peaks.json"))
    return root


# ------------------------------------------------------------------ layout
def test_benchmark_json_resolves_and_is_well_formed():
    assert layout.validate(ROOT) == []
    bench = layout.load_benchmark(ROOT)
    for cell in bench["workloads"]:
        spec = layout.resolve(cell["name"], ROOT)
        assert spec["config"]["dataset"] in datasets.DATASETS
        assert spec["traffic"]["loop"] in ("closed", "open")
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"], cell["name"]


@pytest.mark.parametrize("bad", [
    {"name": "has space"}, {"unit": "tokens per second"},
    {"better": "more"}, {"bound": 0.5}, {"why_not": 1}])
def test_validate_refuses_malformed_entries(tiny_root, bad):
    bench = json.loads(json.dumps(TINY_BENCH))
    bench["end_to_end"][0].update(bad)
    _write(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    assert layout.validate(tiny_root)


def test_new_config_traffic_and_metric_are_found_without_code(tiny_root):
    bench = json.loads(json.dumps(TINY_BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tiny2",
                             "file": "bench/configs/tiny2.json"})
    bench["workloads"].append({"name": "tiny2.scan_like", "config": "tiny2",
                               "traffic": "wide", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("tiny2.scan_like")
    bench["per_layer"].append(
        {"name": "answer_count.wide", "unit": "ids", "better": "higher",
         "source": "program_counter", "layer": "RPC and service",
         "moves": "read_p90_ms", "workloads": ["tiny2.scan_like"]})
    _write(os.path.join(tiny_root, "BENCHMARK.json"), bench)
    _write(os.path.join(tiny_root, "bench", "configs", "tiny2.json"),
           {**TINY_CONFIG, "name": "tiny2", "raw_mib": 2})
    _write(os.path.join(tiny_root, "bench", "traffic", "wide.json"),
           {**TINY_TRAFFIC["tiny_gather"], "fanout": 512})
    with open(os.path.join(tiny_root, "bench", "metrics",
                           "answer_count.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['counters']['lookups']\n")
    assert layout.validate(tiny_root) == []
    spec = layout.resolve("tiny2.scan_like", tiny_root)
    assert spec["config"]["raw_mib"] == 2
    assert spec["traffic"]["fanout"] == 512
    assert [m["name"] for m in spec["per_layer"]] == ["answer_count.wide"]
    reader = layout.metric_reader("answer_count.wide", tiny_root)
    assert reader({"counters": {"lookups": 7}}) == 7
    with pytest.raises(layout.LayoutError):
        layout.resolve("tiny3.none", tiny_root)


# ------------------------------------------------------- data and schedule
@pytest.mark.parametrize("name", sorted(datasets.DATASETS))
def test_generator_is_deterministic_per_seed(name):
    a = datasets.generate(name, 64 << 10, 2**31 + 5)
    b = datasets.generate(name, 64 << 10, 2**31 + 5)
    c = datasets.generate(name, 64 << 10, 6)
    assert a == b and a != c
    assert sum(map(len, a)) >= 64 << 10 > sum(map(len, a[:-1]))
    assert all(isinstance(s, bytes) and s for s in a)


@pytest.mark.parametrize("config", sorted(
    c["name"] for c in layout.load_benchmark()["configs"]))
def test_config_shape_gives_the_published_average_length(config):
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    strings = datasets.generate(cfg["dataset"], 2 << 20, 2**31 + 7,
                                cfg["dataset_shape"])
    avg = sum(map(len, strings)) / len(strings)
    assert avg == pytest.approx(cfg["published"]["avg_len_bytes"], rel=0.02)


def test_zipf_cdf_matches_clipped_zipf():
    cdf = datasets.zipf_cdf(600)
    pmf = np.diff(np.concatenate(([0.0], cdf)))
    # pmf[0] = 1 / zeta(1.15); a long partial sum plus the integral tail
    k = np.arange(1, 4_000_001, dtype=np.float64)
    zeta = np.sum(k ** -1.15) + 4e6 ** -0.15 / 0.15
    assert 1.0 / pmf[0] == pytest.approx(zeta, rel=1e-7)
    assert pmf[9] == pytest.approx(10 ** -1.15 / zeta)
    assert cdf[-1] == 1.0 and np.all(pmf > 0)


@pytest.mark.parametrize("ids", [
    {"dist": "uniform", "unique": True, "sorted": True},
    {"dist": "zipf", "zipf_s": 0.99, "scatter": 2654435761}])
def test_schedule_is_deterministic_per_seed(ids):
    def draw(seed):
        s = schedule.IdSampler(ids, 100_000)
        reqs = schedule.requests(s, schedule.stream(seed, schedule.WINDOW, 0),
                                 5, 64)
        at = schedule.arrivals(500.0, schedule.stream(seed, 9), 2.0)
        return np.concatenate(reqs), at

    (a, at_a), (b, at_b), (c, at_c) = draw(2**31 + 9), draw(2**31 + 9), draw(3)
    assert np.array_equal(a, b) and np.array_equal(at_a, at_b)
    assert not np.array_equal(a, c) and not np.array_equal(at_a, at_c)
    assert a.min() >= 0 and a.max() < 100_000
    assert np.all(np.diff(at_a) > 0) and at_a[-1] < 2.0
    if ids.get("unique"):
        for req in np.split(a, 5):
            assert np.all(np.diff(req) > 0)


# ------------------------------------------------------------- arithmetic
def test_p99_is_nearest_rank_and_failures_lie_beyond():
    assert latency.percentile(list(range(1, 101)), 99) == 99
    assert latency.percentile(list(range(1, 201)), 99) == 198
    assert latency.percentile([5.0], 99) == 5.0
    one_failed = [1.0] * 99 + [math.inf]
    assert latency.percentile(one_failed, 99) == 1.0
    two_failed = [1.0] * 98 + [math.inf] * 2
    assert latency.percentile(two_failed, 99) == math.inf
    assert latency.beyond(one_failed, 1.0) == 1
    win = drive.Window([1, 2, 3], multi=False)
    win.t_ref[:] = 0.0
    win.t_done[:] = [0.25, 0.1, 0.5]
    win.answers = [b"x", None, b"y"]
    win.errors = {1: "boom"}
    win.sent = 3
    assert list(win.latencies_s()) == [0.25, math.inf, 0.5]
    win.close(0.0, 0.4)
    assert win.seconds == 0.5
    assert run.compare(win, [b"", b"x", b"?", b"y"]) == {
        "wrong": 0, "failed": 1, "right": 2}


def test_trace_busy_union_idle_share_and_gaps():
    device = [[("fusion", 1.0, 2.0, True), ("decode_compact", 1.5, 3.0, True),
               ("copy-start", 5.0, 5.5, True), ("dma", 5.5, 6.0, False),
               ("fusion", 9.0, 12.0, True)]]
    host = [("bench.rpc", 0.0, 10.0), ("bench.store.multiget", 3.0, 4.5),
            ("bench.kernel.decode_batch", 4.0, 5.0)]
    out = btrace.reduce_events(device, host, (0.0, 10.0))
    assert out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(2.0 + 1.0 + 1.0)
    assert out["compute_s"] == pytest.approx(2.0 + 1.0)   # no transfers
    assert out["busy_s_per_device"] == [out["busy_s"]]
    assert out["compute_s_per_device"] == [out["compute_s"]]
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({"idle:kernel.decode_batch": 1.0,
                                  "idle:store.multiget": 0.0 + 1.0,
                                  "idle:rpc": 1.0 + 3.0})
    assert sum(gaps.values()) == pytest.approx(10.0 - out["busy_s"])
    assert dict(out["device_ops"])["fusion"] == pytest.approx(2.0)
    read = layout.metric_reader("device_idle_share.gather")
    assert read({"trace": out}) == pytest.approx(60.0)


def test_op_label_reads_hlo_text():
    name = ("%branch_0_fun.1 = s32[256,1,256]{2,1,0:T(1,128)S(1)} "
            "custom-call(s32[256,8]{1,0:T(8,128)S(1)} %copy.20)")
    assert btrace.op_label(name) == "custom-call s32[256,1,256] %branch_0_fun.1"
    assert btrace.op_label("decode_compact") == "decode_compact"
    tuple_op = ("%copy-start.1 = (s32[12119,16]{0,1:T(8,128)S(1)}, "
                "s32[12119,16]{1,0}) copy-start(s32[12119,16] %p)")
    assert btrace.op_label(tuple_op) == "%copy-start.1"


def test_interval_helpers():
    a = btrace.union([(3, 4), (0, 2), (1, 2.5), (6, 6)])
    assert a == [(0, 2.5), (3, 4)]
    assert btrace.intersect(a, [(2, 3.5)]) == [(2, 2.5), (3, 3.5)]
    assert btrace.subtract([(0, 10)], a) == [(2.5, 3), (4, 10)]
    assert btrace.total(a) == 3.5


def test_roofline_counts_useful_bytes_and_counter_metrics():
    assert roofline.decode_useful_bytes(100, 500) == 100 * 18 + 500
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert peak == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    counters = {"lookups": 1000, "decoded_strings": 300, "padded_rows": 512,
                "decoded_bytes": 9000, "cache_hits": 700, "cache_misses": 300,
                "rpc_multiget": 40, "rpc_get": 0, "real_tokens": 2000}
    ctx = {"counters": counters, "peaks": {"hbm_bytes_per_s": peak},
           "trace": {"compute_s": 1e-4, "busy_s": 2e-4, "window_s": 1.0,
                     "compute_s_per_device": [1e-4],
                     "busy_s_per_device": [2e-4]},
           "latency": {"p50_ms": 2.5, "p90_ms": 7.25, "p95_ms": 8.0,
                       "p99_ms": 9.0}}
    read = layout.metric_reader
    assert read("decode_roofline.gather")(ctx) == pytest.approx(
        100 * (2000 * 18 + 9000) / (1e-4 * 819e9))
    assert read("pad_row_share.get")(ctx) == pytest.approx(
        100 * (1 - 300 / 512))
    assert read("cache_hit_share.get")(ctx) == pytest.approx(70.0)
    assert read("ids_per_rpc.get")(ctx) == pytest.approx(25.0)
    assert read("read_p90_ms.get")(ctx) == 7.25
    failed = {**ctx, "latency": {**ctx["latency"], "p90_ms": math.inf}}
    assert read("read_p90_ms.get")(failed) == sys.float_info.max
    idle = {**ctx, "counters": {**counters, "padded_rows": 0,
                                "real_tokens": 0}}
    assert read("pad_row_share.get")(idle) is None
    assert read("decode_roofline.get")(idle) is None


# -------------------------------------------------------------- whole runs
@pytest.mark.parametrize("workload,fault,correct", [
    ("tiny.gather", None, True),
    ("tiny.get", None, True),
    ("tiny.gather", "lossy8", False),     # the control
    ("tiny.get", "flip", False),          # a byte altered where produced
    ("tiny.gather", "half", False),       # half of each decode batch left out
    ("tiny.gather", "drop", False),       # answers that never come
    ("tiny.gather", "bypass", False),     # right answers, none from the kernel
])
def test_tiny_run_decides_correct(tiny_root, capsys, workload, fault,
                                  correct):
    argv = ["--workload", workload, "--seed", str(2**31 + 11),
            "--seconds", "0.5", "--trace", "0"]
    if fault:
        argv += ["--fault", fault]
    # platform=None skips the look for a TPU; everything else is the run
    assert run.main(argv, root=tiny_root, platform=None) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is correct
    assert list(result)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check kernel_strings")
    checks = result["checks"]
    assert (checks["kernel_strings"]["value"] >= 1) is (fault != "bypass")
    broken = {"drop": checks["failed_requests"]["value"] > 0,
              "bypass": checks["kernel_strings"]["value"] < 1}.get(
                  fault, checks["wrong_strings"]["value"] > 0)
    assert broken is (not correct)
    tail = "read_p90_ms" if workload == "tiny.gather" else "read_p50_ms"
    assert set(result["metrics"]) == {tail, "setup_s", "bytes_per_raw_byte"
                                      } | ({"strings_per_s"} if tail ==
                                           "read_p90_ms" else set())
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0
    assert (result["failed"] > 0) is (fault == "drop")


def test_run_without_tpu_fails_with_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "access_titles.gather", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no tpu found" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_without_the_program_fails_with_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "access_titles.gather", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "the program is missing" in proc.stderr
    assert proc.stdout.strip() == ""
