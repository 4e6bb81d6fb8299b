"""Tiny checkouts for the chip benchmark's CPU rehearsals.

``make_root`` copies ``BENCHMARK.json`` and the benchmark's data files
into a temporary checkout, with every configuration and traffic mix cut
to a size the CPU serves in seconds (tables of 2,048 slots, pools of 32
templates, ticks of at most 128 packets); the cells, their metrics and
the harness code are the committed ones.
"""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
TINY_CFG = dict(n_buckets=256, bucket_size=8, train_flows=384)
TINY_MIX = dict(pool=32, concurrency=64, max_tick=128)
TINY_RATE = 4000.0


def shrink(root: str) -> None:
    """Cut every configuration and traffic file under ``root`` in place."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        path = os.path.join(root, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(TINY_CFG)
        with open(path, "w") as f:
            json.dump(cfg, f)
    tdir = os.path.join(root, "benchmarks", "chip", "traffic")
    for name in os.listdir(tdir):
        path = os.path.join(tdir, name)
        with open(path) as f:
            mix = json.load(f)
        mix.update(TINY_MIX)
        if "rate_pkts_per_s" in mix:
            mix["rate_pkts_per_s"] = TINY_RATE
        with open(path, "w") as f:
            json.dump(mix, f)


def make_root(dst: str, tiny: bool = True) -> str:
    os.makedirs(os.path.join(dst, "benchmarks"), exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    if tiny:
        shrink(dst)
    return dst
