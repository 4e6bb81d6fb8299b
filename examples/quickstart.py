"""Quickstart: the complete SpliDT pipeline in one script.

    PYTHONPATH=src python examples/quickstart.py

Synthetic flows -> windowed features -> Algorithm-1 partitioned training
-> range-marking rules -> data-plane engine inference -> resource +
recirculation reports.  Training and the resource models run on the
host; the engine's walk (``impl="ref"``: dense jnp, no Pallas kernel)
runs on JAX's default device — the TPU when one is attached, the CPU
otherwise.
"""
import numpy as np

from repro.core.inference import Engine
from repro.core.partition import train_partitioned_dt
from repro.core.recirc import HADOOP, WEBSERVER, recirc_bandwidth
from repro.core.resources import estimate
from repro.core.tree import macro_f1
from repro.flows.synthetic import make_dataset
from repro.flows.windows import window_features, window_packets


def main():
    print("=== SpliDT quickstart ===")
    ds = make_dataset("d2", n_flows=3000)
    train, test = ds.split()
    P, K = 3, 4
    print(f"dataset: {ds.name}, {ds.n_flows} flows, {ds.n_classes} classes; "
          f"partitions={P}, k={K} feature registers/flow")

    Xw = window_features(train, P)
    pdt = train_partitioned_dt(Xw, train.labels,
                               partition_sizes=[3, 3, 3], k=K)
    per_part, per_sub = pdt.feature_density()
    print(f"trained {len(pdt.subtrees)} subtrees, total depth "
          f"{pdt.total_depth}; unique features "
          f"{len(pdt.unique_features())} (vs k={K} registers); "
          f"density/subtree {per_sub:.1f}%")

    # data-plane engine (feature_window + dt_traverse kernels)
    wp = window_packets(test, P)
    res = Engine.from_model(pdt, impl="ref").run(wp)
    f1 = macro_f1(test.labels, res.labels, ds.n_classes)
    print(f"engine F1 = {f1:.3f}; mean recirculations/flow = "
          f"{res.recircs.mean():.2f}")

    rep = estimate(pdt, flows=500_000)
    print(f"resources: {rep.tcam_entries} TCAM entries "
          f"({rep.tcam_bits / 1e6:.2f} Mb), "
          f"{rep.register_bits_per_flow} register bits/flow, "
          f"capacity {rep.flow_capacity:,} flows, "
          f"feasible@500K={rep.feasible}")
    for env in (WEBSERVER, HADOOP):
        bw = recirc_bandwidth(res.recircs, 1_000_000, env)
        print(f"recirculation @1M flows [{env.name}]: "
              f"{bw.mean_mbps:.1f} Mbps "
              f"({bw.fraction_of_budget * 100:.4f}% of the 100G path)")


if __name__ == "__main__":
    main()
