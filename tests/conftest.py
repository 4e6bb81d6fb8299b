import os
import subprocess
import sys

import pytest

# NOTE: no XLA_FLAGS here on purpose -- unit tests and benches must see
# ONE device; only launch/dryrun.py (and subprocess helpers below) force
# a host-device count.

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, devices: int = 8, timeout: int = 600):
    """Run python code in a subprocess with N fake host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
    return out.stdout


@pytest.fixture(scope="session")
def small_flow_ds():
    from repro.flows.synthetic import make_dataset
    return make_dataset("d2", n_flows=1200)


@pytest.fixture(scope="session")
def trained_pdt(small_flow_ds):
    from repro.core.partition import train_partitioned_dt
    from repro.flows.windows import window_features
    tr, _ = small_flow_ds.split()
    Xw = window_features(tr, 3)
    pdt = train_partitioned_dt(Xw, tr.labels, partition_sizes=[2, 3, 2], k=4)
    return pdt, Xw, tr
