"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per harness spec): us_per_call
is the per-string (or per-query) cost of the benchmark's primary operation;
`derived` carries the table's headline metric.

  PYTHONPATH=src python -m benchmarks.run            # standard (4 MiB/dataset)
  PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized (1 MiB)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale-ish (16 MiB)
  PYTHONPATH=src python -m benchmarks.run --only table3,kernels
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "results", "bench")


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.3f},{derived}", flush=True)


def _dump(name: str, obj) -> None:
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=str)


def bench_table1(size_mib: int) -> None:
    from benchmarks.paper_tables import table1_dict_size_sweep
    rows = table1_dict_size_sweep(size_mib)
    _dump("table1", rows)
    for r in rows:
        _emit(f"table1/bits{r['bits']}", r["access_ns"] / 1e3,
              f"ratio={r['ratio']};decomp_mib_s={r['decomp_mib_s']};"
              f"dict_mib={r['dict_mib']};tok_len={r['token_len']}")


def bench_table3(size_mib: int) -> None:
    from benchmarks.paper_tables import table3_main_comparison
    rows = table3_main_comparison(size_mib)
    _dump("table3", [vars(m) for m in rows])
    for m in rows:
        _emit(f"table3/{m.dataset}/{m.compressor}", m.access_ns / 1e3,
              f"ratio={m.ratio:.3f};comp_mib_s={m.comp_mib_s:.2f};"
              f"decomp_mib_s={m.decomp_mib_s:.1f}")


def bench_table4(size_mib: int) -> None:
    from benchmarks.paper_tables import table4_dict_footprint
    rows = table4_dict_footprint(size_mib)
    _dump("table4", rows)
    for r in rows:
        _emit(f"table4/{r['dataset']}/{r['compressor']}", 0.0,
              f"total_mib={r['total_mib']};data_mib={r['data_mib']};"
              f"entries={r['entries']}")


def bench_table5(size_mib: int) -> None:
    from benchmarks.paper_tables import table5_train_parse_breakdown
    rows = table5_train_parse_breakdown(size_mib)
    _dump("table5", rows)
    for r in rows:
        _emit(f"table5/{r['dataset']}/{r['compressor']}", 0.0,
              f"training_s={r['training_s']};parsing_s={r['parsing_s']}")


def bench_figures(size_mib: int) -> None:
    from benchmarks import paper_figures as pf
    for name, fn in [("fig2", pf.fig2_threshold_sweep),
                     ("fig3", pf.fig3_gain_by_length),
                     ("fig6", pf.fig6_bucket_sizes),
                     ("fig8", pf.fig8_smoothed_gain),
                     ("fig9", pf.fig9_token_length_distribution),
                     ("fig10", pf.fig10_coverage)]:
        t0 = time.perf_counter()
        rows = fn(size_mib)
        _dump(name, rows)
        head = rows[0] if rows else {}
        tail = rows[-1] if rows else {}
        _emit(name, (time.perf_counter() - t0) * 1e6 / max(1, len(rows)),
              f"first={head};last={tail}".replace(",", ";"))


def bench_kernels(size_mib: int) -> None:
    """OnPair device-codec throughput (jit ref path; on a CPU the Pallas
    kernels run interpreted, and interpreted timing is not meaningful)."""
    import numpy as np

    from benchmarks.common import dataset
    from repro.core import make_onpair16
    from repro.kernels.ops import OnPairDevice

    strings = dataset("book_titles", max(1, size_mib // 2) << 20)
    comp = make_onpair16(sample_bytes=2 << 20)
    comp.train(strings)
    dev = OnPairDevice(comp.dictionary)
    corpus = comp.compress(strings[:20000])
    tokens = np.asarray(corpus.payload.view("<u2"), dtype=np.int32)
    raw = sum(len(s) for s in strings[:20000])
    # warmup + timed decode
    dev.decode_stream(tokens, use_pallas=False)
    t0 = time.perf_counter()
    out = dev.decode_stream(tokens, use_pallas=False)
    dt = time.perf_counter() - t0
    assert out == b"".join(strings[:20000])
    _emit("kernels/decode_stream_jit", dt / max(1, len(tokens)) * 1e6,
          f"mib_s={raw / (1 << 20) / dt:.1f}")
    batch = strings[:256]
    dev.encode_to_bytes(batch, use_pallas=False)
    t0 = time.perf_counter()
    dev.encode_to_bytes(batch, use_pallas=False)
    dt = time.perf_counter() - t0
    bb = sum(len(s) for s in batch)
    _emit("kernels/encode_batch_jit", dt / len(batch) * 1e6,
          f"mib_s={bb / (1 << 20) / dt:.2f}")


def bench_store(size_mib: int) -> None:
    """repro.store serving path: batched multiget vs naive access loop."""
    from benchmarks.store_bench import store_multiget_bench
    rows = store_multiget_bench(size_mib)
    _dump("store", rows)
    for r in rows:
        us = r["total_s"] / max(1, r["n_queries"]) * 1e6
        _emit(f"store/{r['variant']}/{r['backend']}", us,
              f"lookups_s={r['lookups_per_s']};mib_s={r['mib_s']};"
              f"p50_us={r['p50_us']};p99_us={r['p99_us']};"
              f"per={r['latency_per']}")


def bench_ingest(size_mib: int) -> None:
    """Write path: frozen-dictionary appends + drift-triggered compaction."""
    from benchmarks.store_bench import store_ingest_bench
    rows = store_ingest_bench(size_mib)
    _dump("ingest", rows)
    for r in rows:
        us = r["total_s"] / max(1, r["n_strings"]) * 1e6
        derived = f"strings_s={r['strings_per_s']}"
        if "mib_s" in r:
            derived += f";mib_s={r['mib_s']}"
        if "ratio_after" in r:
            derived += (f";ratio_before={r['ratio_before']};"
                        f"ratio_after={r['ratio_after']};"
                        f"drift={r['drift_at_trigger']}")
        _emit(f"ingest/{r['dataset']}/{r['op']}", us, derived)


def bench_rpc(size_mib: int) -> None:
    """Multi-process shard serving: loopback RPC vs in-process routing."""
    from benchmarks.rpc_bench import rpc_bench
    rows = rpc_bench(size_mib)
    _dump("rpc", rows)
    for r in rows:
        us = r["total_s"] / max(1, r["n"]) * 1e6
        rate = ("lookups_s=" + str(r["lookups_per_s"])
                if "lookups_per_s" in r
                else "strings_s=" + str(r["strings_per_s"]))
        _emit(f"rpc/{r['op']}/{r['transport']}", us,
              f"{rate};p50_us={r['p50_us']};p99_us={r['p99_us']};"
              f"per={r['latency_per']}")


def bench_client(size_mib: int) -> None:
    """Client API v3: one session over shard:// (in-process) and tcp://
    (loopback RPC), sync vs pipelined-async multiget."""
    from benchmarks.client_bench import client_bench
    rows = client_bench(size_mib)
    _dump("client", rows)
    for r in rows:
        us = r["total_s"] / max(1, r["n"]) * 1e6
        _emit(f"client/{r['op']}/{r['transport']}", us,
              f"lookups_s={r['lookups_per_s']};p50_us={r['p50_us']};"
              f"p99_us={r['p99_us']};per={r['latency_per']}")


def bench_locate(size_mib: int) -> None:
    """Reverse lookup: locate hit/miss + scan_prefix over the store
    directly, shard:// and tcp://."""
    from benchmarks.locate_bench import locate_bench
    rows = locate_bench(size_mib)
    _dump("locate", rows)
    for r in rows:
        us = r["total_s"] / max(1, r["n"]) * 1e6
        _emit(f"locate/{r['op']}/{r['transport']}", us,
              f"lookups_s={r['lookups_per_s']};p50_us={r['p50_us']};"
              f"p99_us={r['p99_us']};per={r['latency_per']}")


def bench_loadgen(size_mib: int) -> None:
    """SLO-gated load harness: closed + open loop against a spawned
    2-shard cluster; derived carries the server-side percentiles."""
    from benchmarks.loadgen_bench import loadgen_bench
    rows = loadgen_bench(size_mib, duration_s=2.0 if size_mib <= 1 else 4.0)
    _dump("loadgen", rows)
    for r in rows:
        us = r["duration_s"] / max(1, r["n"]) * 1e6
        _emit(f"loadgen/{r['loop']}/{r['transport']}", us,
              f"ops_s={r['ops_s']};server_p50_us={r['server_p50_us']};"
              f"server_p99_us={r['server_p99_us']};"
              f"goodput_rps={r['goodput_rps']};"
              f"client_p99_us={r['client_p99_us']}")


def bench_tier(size_mib: int) -> None:
    """Tiered storage: memory shed by demotion, RLZ cold-tier ratio, and
    the hot-vs-cold batched read cost (byte-identity asserted inside)."""
    from benchmarks.tier_bench import tier_bench
    rows = tier_bench(size_mib)
    _dump("tier", rows)
    for r in rows:
        op = r["op"]
        if op.startswith("multiget"):
            us = r["total_s"] / max(1, r["n"]) * 1e6
            _emit(f"tier/{op}/store", us,
                  f"lookups_per_s={r['lookups_per_s']};p50_us={r['p50_us']};"
                  f"p99_us={r['p99_us']}")
        elif op == "memory-drop":
            _emit("tier/memory-drop/cold", r["total_s"] * 1e6,
                  f"memory_drop_pct={r['memory_drop_pct']};"
                  f"before_bytes={r['before_bytes']};"
                  f"after_bytes={r['after_bytes']};n_segments={r['n']}")
        else:  # rlz-ratio
            _emit("tier/rlz-ratio/cold", 0.0,
                  f"rlz_ratio={r['rlz_ratio']};raw_bytes={r['raw_bytes']};"
                  f"rlz_bytes={r['rlz_bytes']};"
                  f"segments_per_s={r['segments_per_s']}")


def bench_persist(size_mib: int) -> None:
    """Artifact save/load + store.open latency vs retrain-from-scratch."""
    from benchmarks.persist_bench import persist_bench
    rows = persist_bench(size_mib)
    _dump("persist", rows)
    for r in rows:
        _emit(f"persist/{r['dataset']}/{r['codec']}", r["open_s"] * 1e6,
              f"speedup_vs_retrain={r['speedup_vs_retrain']};"
              f"train_s={r['train_s']};save_s={r['save_s']};"
              f"disk_mib={r['disk_bytes'] / (1 << 20):.2f}")


def bench_roofline(_size_mib: int) -> None:
    """Surface the dry-run roofline summary as bench rows."""
    from repro.launch.roofline import fmt_row, load_records
    for mesh in ("16x16", "2x16x16"):
        for rec in load_records(mesh):
            if rec.get("tag") not in ("", "final"):
                continue
            r = fmt_row(rec)
            tag = rec.get("tag") or "baseline"
            _emit(f"roofline/{mesh}/{r['arch']}/{r['shape']}/{tag}",
                  max(r["t_compute_s"], r["t_memory_s"],
                      r["t_collective_s"]) * 1e6,
                  f"bottleneck={r['bottleneck']};frac={r['roofline_frac']}")


ALL = {
    "table1": bench_table1,
    "table3": bench_table3,
    "table4": bench_table4,
    "table5": bench_table5,
    "figures": bench_figures,
    "kernels": bench_kernels,
    "store": bench_store,
    "ingest": bench_ingest,
    "persist": bench_persist,
    "rpc": bench_rpc,
    "client": bench_client,
    "locate": bench_locate,
    "loadgen": bench_loadgen,
    "tier": bench_tier,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    size = 1 if args.quick else (16 if args.full else 4)
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(ALL)
    print("name,us_per_call,derived")
    for name in names:
        ALL[name](size)


if __name__ == "__main__":
    main()
