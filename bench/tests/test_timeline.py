"""Tests of the readers of the program's spans and copy counters, and of
``bench.timeline``, on the CPU at tiny sizes: each reader on a recorded
``ctx`` and on one from a program that records no spans, the reductions
(span deltas, idle charges, spells) on made-up events, and one whole
traced run of the tiny get cell."""

from __future__ import annotations

import json

import pytest
from bench.tests.test_bench import tiny_root  # noqa: F401  (a fixture)

from bench import layout
from bench import run as brun
from bench import timeline as tl

ROOT = layout.ROOT

#: a recorded window: span totals of both processes, the store's counters
CTX = {
    "counters": {"lookups": 4000, "h2d_bytes": 270_336,
                 "device_strings": 300, "real_tokens": 2000},
    "spans": {
        "kernel.decode_batch": {"count": 8, "seconds": 0.05,
                                "self_seconds": 0.001},
        "kernel.pack": {"count": 8, "seconds": 0.004, "self_seconds": 0.004},
        "kernel.h2d": {"count": 8, "seconds": 0.002, "self_seconds": 0.002},
        "kernel.dispatch": {"count": 8, "seconds": 0.003,
                            "self_seconds": 0.003},
        "kernel.wait": {"count": 8, "seconds": 0.03, "self_seconds": 0.03},
        "kernel.d2h": {"count": 8, "seconds": 0.001, "self_seconds": 0.001},
        "kernel.unpack": {"count": 8, "seconds": 0.006,
                          "self_seconds": 0.006},
        "store.multiget": {"count": 40, "seconds": 0.2,
                           "self_seconds": 0.02},
        "store.decode": {"count": 30, "seconds": 0.16, "self_seconds": 0.01},
        "store.tokens": {"count": 30, "seconds": 0.03, "self_seconds": 0.03},
        "store.cache_put": {"count": 30, "seconds": 0.02,
                            "self_seconds": 0.02},
        "client.coalesce": {"count": 4000, "seconds": 6.0,
                            "self_seconds": 6.0},
        "rpc.multiget": {"count": 40, "seconds": 0.5, "self_seconds": 0.5},
        "server.multiget": {"count": 40, "seconds": 0.3,
                            "self_seconds": 0.05},
    },
}


def read(name):
    return layout.metric_reader(name, ROOT)


def test_readers_of_spans_and_copy_counters():
    assert read("h2d_bytes_per_string.get")(CTX) == pytest.approx(901.12)
    # pack + h2d + dispatch + d2h + unpack self time, per batch
    assert read("copy_ms_per_batch.gather")(CTX) == pytest.approx(
        1e3 * 0.016 / 8)
    assert read("store_prep_us_per_lookup.gather")(CTX) == pytest.approx(
        1e6 * 0.08 / 4000)
    assert read("client_wait_ms_per_get.get")(CTX) == pytest.approx(1.5)
    assert read("wire_ms_per_rpc.get")(CTX) == pytest.approx(1e3 * 0.2 / 40)


@pytest.mark.parametrize("quantity", tl.READERS)
def test_readers_read_nothing_from_a_program_without_spans(quantity):
    """A program that records no spans and no copy counters (the parent of
    the change that added them) gives None, never an error."""
    bare = {"counters": {"lookups": 4000, "real_tokens": 2000}}
    assert read(quantity)(bare) is None
    assert read(quantity)({**bare, "spans": {}}) is None


def test_span_totals_deltas_and_merge():
    def snap(count, seconds):
        return [{"name": "repro_span_total", "labels": {"span": "a"},
                 "value": count},
                {"name": "repro_span_seconds_total", "labels": {"span": "a"},
                 "value": seconds},
                {"name": "repro_span_self_seconds_total",
                 "labels": {"span": "a"}, "value": seconds / 2},
                {"name": "repro_rpc_requests_total", "labels": {"op": "get"},
                 "value": 9}]

    before, after = tl.span_totals(snap(3, 1.0)), tl.span_totals(snap(5, 2.0))
    assert after == {"a": {"count": 5, "seconds": 2.0, "self_seconds": 1.0}}
    win = tl.span_delta(after, before)
    assert win == {"a": {"count": 2, "seconds": 1.0, "self_seconds": 0.5}}
    assert tl.span_delta(after, after) == {}
    both = tl.merge_spans(win, {"b": {"count": 1, "seconds": 0.1,
                                      "self_seconds": 0.1}}, win)
    assert both["a"] == {"count": 4, "seconds": 2.0, "self_seconds": 1.0}
    assert set(both) == {"a", "b"}


def test_idle_goes_to_the_innermost_span_running():
    idle = [(0, 10), (20, 30)]
    layers = [("server:kernel.wait", [(2, 4)]),
              ("server:store.decode", [(1, 8)]),
              ("client:rpc.multiget", [(0, 25)])]
    assert tl.charge_idle(idle, layers) == {
        "server:kernel.wait": 2, "server:store.decode": 5,
        "client:rpc.multiget": 3 + 5, "none": 5}
    assert tl.order(["b", "kernel.wait", "a", "kernel.pack"],
                    tl.SERVER_ORDER) == ["kernel.pack", "kernel.wait", "a",
                                         "b"]


def test_spells_and_what_was_open_across_them():
    done = [0.0, 0.01, 0.02, 0.2, 0.21, 0.5, 9.0]
    assert tl.spells(done, 0.0, 1.0) == [(0.02, 0.2), (0.21, 0.5)]
    spans = [("client.get", 0.0, 0.3), ("rpc.multiget", 0.01, 0.25),
             ("kernel.wait", 0.3, 0.38), ("store.decode", 0.28, 0.6),
             ("client.coalesce", 0.03, 0.05)]
    got = tl.open_across(spans, 0.02, 0.2, tl.CLIENT_ORDER, unit=1e-3)
    assert got["across"] == ["rpc.multiget", "client.get"]
    # no span began or ended in [0.05, 0.2]: the process sat in the rpc
    assert got["quiet"] == pytest.approx(150)
    assert got["quiet_open"] == ["rpc.multiget", "client.get"]
    assert got["overlapping"] == [("client.coalesce", pytest.approx(20))]
    got = tl.open_across(spans, 0.21, 0.5, tl.SERVER_ORDER)
    assert got["across"] == []
    assert [n for n, _ in got["overlapping"]] == [
        "store.decode", "client.get", "kernel.wait", "rpc.multiget"]
    # the longest stretch with no boundary, [0.38, 0.5], lies in the decode
    assert got["quiet"] == pytest.approx(0.12)
    assert got["quiet_open"] == ["store.decode"]


def test_breakdown_splits_the_decode_batch():
    got = tl.breakdown(CTX["spans"])
    assert got["kernel_ms_per_batch"]["kernel.wait"] == pytest.approx(3.75)
    assert got["cache_put_share_of_decode"] == pytest.approx(0.125)


def test_tiny_traced_get_run(tiny_root, capsys, monkeypatch, tmp_path):  # noqa: F811
    """A whole run of the tiny get cell on the CPU: the answers are right,
    the spans of both processes are there with the store's copy counters,
    every reader reads, and the server's spans map onto their profiler
    annotations."""
    # a work directory of its own: the tiny runs of ``test_bench`` may run
    # at the same time in another process, and each empties its own
    monkeypatch.setattr(brun, "WORK", str(tmp_path / "work"))
    monkeypatch.setattr(tl, "TRACE_DIR", str(tmp_path / "timeline"))
    monkeypatch.setattr(tl, "CLIENT_CAPACITY", 1 << 16)
    out = tmp_path / "timeline.json"
    argv = ["--workload", "tiny.get", "--seed", str(2**31 + 13),
            "--seconds", "0.5", "--out", str(out)]
    assert tl.main(argv, root=tiny_root, platform=None) == 0
    got = json.loads(out.read_text())
    assert got["answers"]["wrong"] == 0 and got["answers"]["failed"] == 0
    assert {"client.get", "client.coalesce", "rpc.multiget",
            "server.multiget", "service.coalesce", "service.batch",
            "store.multiget"} <= set(got["spans"])
    assert got["store"]["lookups"] > 0
    assert set(got["metrics"]) >= {"client_wait_ms_per_get",
                                   "wire_ms_per_rpc",
                                   "store_prep_us_per_lookup"}
    assert got["clock"]["matched"] > 0
    assert got["clock"]["residual_p99_us"] < 1e4
    assert sum(got["idle_s"].values()) == pytest.approx(
        got["window_s"] - got["busy_s"])
    assert capsys.readouterr().out.count("spans: ") == len(got["spans"])
