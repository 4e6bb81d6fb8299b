#!/usr/bin/env python3
"""The check's control: the reference in bfloat16 in the program's place.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \\
        --packets <n>

For each seed, builds the cell's templates and schedule exactly as a run
does, and reads what the check would compare had the served verdicts
come from the reference computed one precision below the
configuration's (float32 registers -> bfloat16): the wrong verdicts
among the flows that complete in the ramp and the first ``--packets``
stream positions after it (a run's window), with the templates that
flip.
The benchmark's own runs never run this; it sets the upper reading of
``wrong_verdicts`` (``PERF.md``).  One JSON line per seed on stdout.
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def control_reading(root: str, name: str, seed: int, window_pkts: int) -> dict:
    from benchmarks.chip import harness, reference, traffic
    from benchmarks.chip import model as model_lib
    spec = harness.load_cell(root, name)
    cfg, mix = spec["cfg"], spec["mix"]
    rng = np.random.default_rng(np.random.SeedSequence([0x7EA1, int(seed)]))
    flows = traffic.make_flows(cfg["dataset"], int(mix["pool"]), rng,
                               len_median=cfg["len_median"],
                               len_sigma=cfg["len_sigma"],
                               min_len=cfg["min_len"], max_len=cfg["max_len"])
    sched = traffic.Schedule(flows, float(mix["concurrency"]), rng)
    plain, _ = model_lib.load(cfg, os.path.join(root, harness.REL, ".cache",
                                                 "models"))
    want = reference.verdicts(plain, flows.pkts, flows.lengths)
    ctl = reference.verdicts(plain, flows.pkts, flows.lengths,
                             dtype="bfloat16")
    done = harness.completed_keys(sched, sched.ramp_pkts + window_pkts)
    served = ctl[traffic.template_of(done, sched.M)]
    numbers = harness.check(np.concatenate([done[:, None], served], axis=1),
                            want, sched.M, done)
    flips = int(np.any(want != ctl, axis=1).sum())
    return {"workload": name, "seed": seed, "templates": sched.M,
            "templates_flipped": flips, "verdicts": int(done.size),
            "wrong_verdicts": numbers["wrong_verdicts"][0]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--packets", type=int, required=True)
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    for s in args.seeds.split(","):
        print(json.dumps(control_reading(ROOT, args.workload, int(s),
                                         args.packets)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
