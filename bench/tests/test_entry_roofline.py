"""Tests of the roofline for entries of any length and of the cells over
unbounded OnPair, on the CPU at tiny sizes.

``entry_decode_roofline`` counts the decode's useful bytes as the ids in,
every entry byte read and the bytes out; on an OnPair16 stream it reaches
``decode_roofline``'s count (one 16-byte row a token) only where every
entry is 16 B. A tiny cell over an unbounded OnPair store runs through the
harness: sound, it is correct and reports the metric; the control
(``lossy8``, which zeroes the back half of every 16-byte row, the chained
continuation rows among them) is not.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest
from bench.tests.test_bench import (TINY_BENCH, TINY_CONFIG, TINY_TRAFFIC,
                                    _write, tiny_root)  # noqa: F401

from bench import layout, roofline, run

PEAK = 819e9


def _entry_reader():
    return layout.metric_reader("entry_decode_roofline.urls_onpair")


def _ctx(real_tokens: int, decoded_bytes: int, compute_s=(1e-4,)) -> dict:
    return {"counters": {"real_tokens": real_tokens,
                         "decoded_bytes": decoded_bytes},
            "peaks": {"hbm_bytes_per_s": PEAK},
            "trace": {"compute_s_per_device": list(compute_s)}}


def test_entry_bytes_are_ids_entry_bytes_and_output():
    module = _entry_reader().__globals__
    assert module["entry_useful_bytes"](100, 5000) == 2 * 100 + 2 * 5000
    # a token of a 71-byte entry: 2 B of id, 71 B read, 71 B written,
    # where one row a token would count 16 B read
    assert module["entry_useful_bytes"](1, 71) == 144
    assert roofline.decode_useful_bytes(1, 71) == 89
    read = _entry_reader()
    assert read(_ctx(2000, 90000, (6e-5, 4e-5))) == pytest.approx(
        100 * (2 * 2000 + 2 * 90000) / (1e-4 * PEAK))
    assert read(_ctx(0, 0)) is None
    assert read({**_ctx(10, 100), "trace": None}) is None


@pytest.mark.parametrize("only16", [True, False])
def test_onpair16_count_reaches_row_count_only_for_16_byte_entries(only16):
    """For an OnPair16 stream of T tokens, ids + entry bytes + output equal
    ids + T rows of 16 B + output exactly where every entry is 16 B."""
    from repro.core import make_onpair16
    from repro.data.synth import load_dataset

    strings = load_dataset("urls", 1 << 18, seed=5)
    comp = make_onpair16(sample_bytes=1 << 18, seed=2)
    comp.train(strings)
    lens = comp.dictionary.lens
    rng = np.random.default_rng(3)
    pool = np.flatnonzero(lens == 16) if only16 else np.arange(lens.size)
    tokens = rng.choice(pool, 500)
    decoded = len(comp.dictionary.decode_tokens(tokens))
    module = _entry_reader().__globals__
    entry = module["entry_useful_bytes"](tokens.size, decoded)
    rows = roofline.decode_useful_bytes(tokens.size, decoded)
    assert (entry == rows) is only16
    assert entry <= rows


def test_new_cells_resolve_with_their_metrics():
    assert layout.validate() == []
    onpair = layout.resolve("access_urls_onpair.gather")
    assert onpair["config"]["codec"] == "onpair"
    assert [m["name"] for m in onpair["end_to_end"]] == [
        "read_p90_ms", "strings_per_s", "bytes_per_raw_byte", "setup_s"]
    # the gather's readers of the cache, the buckets' padding and the
    # device, and the roofline right for entries of any length
    assert [m["name"] for m in onpair["per_layer"]] == [
        "cache_hit_share.gather", "pad_row_share.gather",
        "device_idle_share.gather", "entry_decode_roofline.urls_onpair"]
    # the OnPair16 URL table's shape and store, with unbounded OnPair and
    # the cache cut with the column to an eighth of it
    urls = layout.resolve("ycsb_c_urls.get_open")["config"]
    config = onpair["config"]
    assert config["dataset_shape"] == urls["dataset_shape"]
    assert {**config["store"], "cache_bytes": urls["store"]["cache_bytes"]} \
        == urls["store"]
    assert config["store"]["cache_bytes"] * 8 == config["raw_mib"] << 20
    assert set(config["reduced"]) <= set(config["assumed"])


# -------------------------------------------------------------- whole runs
def _onpair_root(root: str) -> None:
    """Add the cell ``tinyop.gather``: the tiny gather mix over a store
    compressed with unbounded OnPair, reporting the entry roofline."""
    bench = json.loads(json.dumps(TINY_BENCH))
    bench["configs"].append({**bench["configs"][0], "name": "tinyop",
                             "file": "bench/configs/tinyop.json"})
    bench["workloads"].append({"name": "tinyop.gather", "config": "tinyop",
                               "traffic": "tiny_gather", "chips": 1,
                               "why": "closed-loop multigets, OnPair"})
    for m in bench["end_to_end"]:
        if "tiny.gather" in m.get("workloads", []):
            m["workloads"].append("tinyop.gather")
    bench["per_layer"].append(
        {"name": "entry_decode_roofline.tinyop", "unit": "%",
         "better": "higher", "source": "device_trace", "layer": "kernel",
         "moves": "strings_per_s", "workloads": ["tinyop.gather"]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    _write(os.path.join(root, "bench", "configs", "tinyop.json"),
           {**TINY_CONFIG, "name": "tinyop", "dataset": "urls",
            "dataset_shape": {"path_depth": [3, 10]}, "codec": "onpair"})
    # a traced run looks up its device's peaks: give this CPU a row
    peaks_path = os.path.join(root, "bench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    _write(peaks_path, {**peaks, "cpu": peaks["TPU v5 lite"]})


@pytest.mark.parametrize("fault,trace,correct", [
    (None, 1, True),
    ("lossy8", 0, False),     # the control
])
def test_tiny_onpair_run(tiny_root, capsys, monkeypatch, tmp_path, fault,  # noqa: F811
                         trace, correct):
    monkeypatch.setattr(run, "WORK", str(tmp_path / "work"))
    _onpair_root(tiny_root)
    argv = ["--workload", "tinyop.gather", "--seed", str(2**31 + 29),
            "--seconds", "0.5", "--trace", str(trace)]
    if fault:
        argv += ["--fault", fault]
    assert run.main(argv, root=tiny_root, platform=None) == 0
    out, _ = capsys.readouterr()
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is correct
    assert (result["checks"]["wrong_strings"]["value"] > 0) is (not correct)
    assert result["checks"]["kernel_strings"]["value"] >= 1
    # every row bucket compiled at open: none in the window
    assert "compile events in the window 0," in out
    if trace:
        # the reader reads the CPU's trace without raising; a CPU trace has
        # no device compute lines, so it reports nothing there
        assert set(result["metrics"]) <= {"entry_decode_roofline.tinyop"}
