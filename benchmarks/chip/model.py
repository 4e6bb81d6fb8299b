"""The configuration's model: trained once by the program, kept as plain trees.

The model belongs to the configuration, as published weights belong to
a model: it is trained by the program's own trainer
(``repro.core.partition.train_partitioned_dt``) on flows drawn from the
configuration's fixed ``model_seed``; ``--seed`` never touches it.  The
trained trees are written as plain JSON (per subtree: partition, node
arrays, per-leaf next subtree and label) into a git-ignored cache
beside this file, keyed by the fields that decide the model, so later
runs of the cell load it in milliseconds.  The reference reads only this
plain form; the server is built from it through the program's own
classes.
"""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np

from benchmarks.chip import reference, traffic

CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".cache", "models")
# the configuration keys that decide the trained model
MODEL_KEYS = ("dataset", "partition_sizes", "k", "train_flows", "model_seed",
              "len_median", "len_sigma", "min_len", "max_len")


def training_flows(cfg: dict) -> traffic.Flows:
    rng = np.random.default_rng(
        np.random.SeedSequence([0x30DE1, int(cfg["model_seed"])]))
    return traffic.make_flows(cfg["dataset"], int(cfg["train_flows"]), rng,
                              len_median=cfg["len_median"],
                              len_sigma=cfg["len_sigma"],
                              min_len=cfg["min_len"], max_len=cfg["max_len"])


def to_plain(pdt) -> dict:
    """A ``PartitionedDT`` as plain JSON-able trees."""
    return {
        "partition_sizes": [int(x) for x in pdt.partition_sizes],
        "k": int(pdt.k), "n_classes": int(pdt.n_classes),
        "n_features": int(pdt.n_features),
        "subtrees": [{
            "sid": int(st.sid), "partition": int(st.partition),
            "feature": st.tree.feature.astype(int).tolist(),
            "threshold": st.tree.threshold.astype(np.float32).tolist(),
            "left": st.tree.left.astype(int).tolist(),
            "right": st.tree.right.astype(int).tolist(),
            "value": st.tree.value.astype(np.float32).tolist(),
            "leaf_next": {str(k): int(v) for k, v in st.leaf_next_sid.items()},
            "leaf_label": {str(k): int(v) for k, v in st.leaf_label.items()},
        } for st in pdt.subtrees],
    }


def from_plain(model: dict):
    """The program's ``PartitionedDT`` for a plain model."""
    from repro.core.partition import PartitionedDT, SubTree
    from repro.core.tree import Tree

    subtrees = [SubTree(
        sid=st["sid"], partition=st["partition"],
        tree=Tree(feature=np.asarray(st["feature"], np.int32),
                  threshold=np.asarray(st["threshold"], np.float32),
                  left=np.asarray(st["left"], np.int32),
                  right=np.asarray(st["right"], np.int32),
                  value=np.asarray(st["value"], np.float32),
                  n_classes=model["n_classes"]),
        leaf_next_sid={int(k): v for k, v in st["leaf_next"].items()},
        leaf_label={int(k): v for k, v in st["leaf_label"].items()})
        for st in model["subtrees"]]
    return PartitionedDT(subtrees=subtrees,
                         partition_sizes=list(model["partition_sizes"]),
                         k=model["k"], n_classes=model["n_classes"],
                         n_features=model["n_features"])


def train(cfg: dict) -> dict:
    """Train the configuration's model with the program's trainer."""
    from repro.core.partition import train_partitioned_dt

    fl = training_flows(cfg)
    P = len(cfg["partition_sizes"])
    X = reference.window_features(fl.pkts, fl.lengths, P,
                                  range(reference.N_FEATURES))
    n_classes = traffic.DATASETS[cfg["dataset"]][0]
    pdt = train_partitioned_dt(X, fl.labels,
                               partition_sizes=list(cfg["partition_sizes"]),
                               k=int(cfg["k"]), n_classes=n_classes)
    return to_plain(pdt)


def load(cfg: dict, cache_dir: str = CACHE_DIR) -> tuple[dict, bool]:
    """``(plain model, came from the cache)``."""
    key = json.dumps({k: cfg[k] for k in MODEL_KEYS}, sort_keys=True)
    digest = hashlib.sha256(key.encode()).hexdigest()[:16]
    path = os.path.join(cache_dir, f"{cfg['name']}-{digest}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f), True
    model = train(cfg)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(model, f)
    os.replace(tmp, path)
    return model, False
