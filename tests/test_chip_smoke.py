"""CPU rehearsal of ``chip_smoke.py``: every phase the chip runs, at a
tiny size, so the script cannot rot between chip runs.  The device
check and the kernel-presence assertion live only in ``main()`` — here
JAX runs on the CPU and Pallas interprets."""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

TINY = chip_smoke.Sizes(n_flows=1536, n_buckets=64, bucket_size=8,
                        concurrency=64.0, tick=256, micro_batch=256)


@pytest.fixture(scope="module")
def setup():
    return chip_smoke.phase_model(TINY)


def test_phase_model_builds_the_served_model(setup):
    assert setup.pdt.partition_sizes == list(chip_smoke.PARTITIONS)
    assert setup.pdt.k == chip_smoke.K
    n = setup.test.n_flows
    assert n == TINY.n_flows - TINY.n_flows // 3
    assert setup.win_pkts.shape[:2] == (n, len(chip_smoke.PARTITIONS))
    assert all(a.shape == (n,) for a in setup.oracle)
    assert (setup.oracle[2] >= 0).all()   # every flow exits


def test_phase_model_rejects_a_different_tree(setup):
    import copy
    other = copy.deepcopy(setup.pdt)
    other.subtrees[0].tree.threshold[0] += 1.0
    with pytest.raises(AssertionError, match="threshold"):
        chip_smoke._same_model(setup.pdt, other)


def test_phase_batch_matches_oracle(setup):
    chip_smoke.phase_batch(setup, TINY)


def test_phase_serve_matches_oracle(setup):
    chip_smoke.phase_serve(setup, TINY)


def test_match_reports_a_flipped_verdict(setup):
    labels = setup.oracle[0].copy()
    labels[0] += 1
    with pytest.raises(AssertionError, match="labels"):
        chip_smoke._match("probe", labels, *setup.oracle[1:], setup.oracle)


def test_pallas_programs_cover_every_entry_point(setup):
    programs = chip_smoke.pallas_programs(setup, TINY)
    assert set(programs) == {
        "Engine.run[pallas]", "Engine.run[pallas+compact]",
        "Engine.run_streaming[pallas]", "FlowTableServer[pallas] tick"}
    assert all(isinstance(t, str) and t for t in programs.values())


def test_phase_sharded_on_a_one_device_mesh(setup):
    programs = chip_smoke.phase_sharded(setup, TINY, 1)
    assert list(programs) == ["Engine.run_streaming[pallas, mesh of 1]"]


def test_import_repro_refuses_a_foreign_package(tmp_path, monkeypatch):
    """Beside nothing of the repository, the script must not pick up
    another copy of the package."""
    monkeypatch.setattr(chip_smoke, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "path", list(sys.path))
    with pytest.raises(ImportError):
        chip_smoke.import_repro()


def test_main_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, chip_smoke.__file__],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
