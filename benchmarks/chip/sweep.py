#!/usr/bin/env python3
"""Find the open-loop knee of a cell: one set-up, a window per rate.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --fractions 0.5,0.7,0.85,1.0

The cell's configuration and flows are set up once, as a run sets them
up.  The server first runs one saturating window (ticks of the mix's
``max_tick`` back to back), whose delivered rate is the starting point;
then one open-loop window per fraction of that rate, in the order given.
One JSON line per window reports the offered and delivered rates, the
verdict latency percentiles, and the backlog (packets due but not yet
ingested) left at the end of each half of the window: a rate is
sustained when the delivered rate is within 2% of the offered one and
neither half ends more than one tick behind.  The last line gives the
knee (the highest sustained rate) and four fifths of it, the rate an
open-loop cell below capacity runs at, and which such a cell's traffic
file records.  The traffic file's own arrivals and rate are not used;
the benchmark's runs never run this.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
# libtpu writes its logs under /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fractions", required=True)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from benchmarks.chip import harness
    cell = harness.Served(ROOT, args.workload, args.seed, t_start=T_START)
    sat = cell.window(args.seconds, None)
    sat_rate = sat["pkts_in_window"] / args.seconds
    print(json.dumps({"rate": None, "delivered": sat_rate,
                      "calls": len(sat["calls"]),
                      "compiles": sat["compiles"]}), flush=True)
    knee = None
    for frac in (float(f) for f in args.fractions.split(",")):
        rate = float(round(frac * sat_rate, -3))
        half = cell.window(args.seconds / 2, rate)
        rest = cell.window(args.seconds / 2, rate)
        lat = np.concatenate([cell.latencies(half)[0],
                              cell.latencies(rest)[0]])
        calls = half["calls"] + rest["calls"]
        delivered = ((half["pkts_in_window"] + rest["pkts_in_window"])
                     / args.seconds)
        kept_up = (delivered >= 0.98 * rate
                   and max(half["backlog_pkts"], rest["backlog_pkts"])
                   <= cell.tick)
        if kept_up:
            knee = max(knee or 0.0, rate)
        print(json.dumps({
            "rate": rate, "fraction": frac, "delivered": delivered,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "backlog_half1": half["backlog_pkts"],
            "backlog_half2": rest["backlog_pkts"],
            "sustained": bool(kept_up), "calls": len(calls),
            "tick_pkts_mean": float(np.mean([b - a for a, b, _, _ in calls])),
            "compiles": half["compiles"] + rest["compiles"]}), flush=True)
    print(json.dumps({"saturating": sat_rate, "knee": knee,
                      "open_rate": (float(round(0.8 * knee, -3))
                                    if knee else None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
