"""Find the knee of an open-loop cell: one set-up, then a window per rate.

    python -m bench.sweep --workload ycsb_c_urls.get_open --seed 7 \\
        --seconds 5 --rates 2000 4000 8000 16000

The cell's traffic file is used as it stands except for its ``rate``. For
each rate the sweep prints the requests sent, the completions per second,
the p50 and p99 from the due time, the late sends, how long the last answer
came after the window's close (a backlog that grows through the window
shows here), and whether every answer was right. The knee is the highest
rate that completes what it is offered with no growing backlog; a cell
below the knee runs at about four fifths of it, and ``PERF.md`` records the
sweep that set its rate. Runs on the chip only, like ``bench.run``.
"""

from __future__ import annotations

import argparse
import json
import sys

from bench import layout
from bench.latency import percentile
from bench.run import PLATFORM, ROOT, RunError, compare, log, serving, window


def main(argv=None, root: str = ROOT, platform: str | None = PLATFORM
         ) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = layout.resolve(args.workload, root)
    if spec["traffic"]["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    rows = []
    try:
        with serving(spec, args.seed, platform) as s:
            for k, rate in enumerate(args.rates):
                traffic = {**spec["traffic"], "rate": rate}
                win = window(traffic, s["clients"], s["sampler"],
                             args.seed + k + 1, args.seconds)
                got = compare(win, s["strings"])
                lat = list(win.latencies_s() * 1e3)
                row = {"rate": rate, "sent": win.sent,
                       "completed_per_s": (win.sent - got["failed"])
                       / win.seconds,
                       "p50_ms": percentile(lat, 50),
                       "p99_ms": percentile(lat, 99), "late": win.late,
                       "drain_s": win.seconds - args.seconds,
                       "wrong": got["wrong"], "failed": got["failed"]}
                log("sweep " + json.dumps(row))
                rows.append(row)
    except (RunError, layout.LayoutError) as exc:
        print(f"sweep: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"workload": args.workload, "sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
