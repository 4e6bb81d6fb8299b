"""The chip benchmark's plain reference against the program's own oracle,
and the model's plain form."""
import json

import numpy as np
import pytest

from benchmarks.chip import model as model_lib
from benchmarks.chip import reference, traffic

from chipbench_tiny import make_root

CFG = {"name": "probe", "dataset": "d2", "partition_sizes": [2, 3, 2],
       "k": 4, "train_flows": 600, "model_seed": 3, "len_median": 40.0,
       "len_sigma": 0.7, "min_len": 12, "max_len": 192}


@pytest.fixture(scope="module")
def plain():
    return model_lib.train(CFG)


@pytest.fixture(scope="module")
def flows():
    return traffic.make_flows("d2", 300, np.random.default_rng(11))


def test_feature_table_is_the_programs():
    from repro.core.features import FEATURE_TABLE, REGISTRY
    assert reference.N_FEATURES == len(REGISTRY)
    np.testing.assert_array_equal(np.asarray(reference.FEATURES),
                                  FEATURE_TABLE[:, :3])


def test_window_features_equal_the_programs(flows):
    from repro.flows.synthetic import FlowDataset
    from repro.flows.windows import window_features
    ds = FlowDataset(flows.pkts, flows.lengths, flows.labels, 4, "d2")
    for p in (3, 4):
        want = window_features(ds, p)
        got = reference.window_features(flows.pkts, flows.lengths, p,
                                        range(reference.N_FEATURES))
        np.testing.assert_array_equal(got, want)


def test_predict_equals_partitioned_dt(plain, flows):
    pdt = model_lib.from_plain(plain)
    X = reference.window_features(flows.pkts, flows.lengths, 3,
                                  range(reference.N_FEATURES))
    want = np.stack(pdt.predict(X, return_trace=True), axis=1)
    np.testing.assert_array_equal(
        reference.verdicts(plain, flows.pkts, flows.lengths), want)


def test_plain_model_round_trips(plain):
    again = model_lib.to_plain(model_lib.from_plain(
        json.loads(json.dumps(plain))))
    assert again == plain


def test_model_cache_trains_once(tmp_path):
    a, cached_a = model_lib.load(CFG, str(tmp_path))
    b, cached_b = model_lib.load(CFG, str(tmp_path))
    assert (cached_a, cached_b) == (False, True) and a == b


def test_bfloat16_control_is_rejected(tmp_path):
    """The control on a cell's own template pool and model: registers in
    bfloat16 flip verdicts the float32 reference gives, so the check
    fails it."""
    from benchmarks.chip.control import control_reading
    root = make_root(str(tmp_path), tiny=False)
    r = control_reading(root, "d2-1M-steady-sat", 2**31 + 5, 1 << 22)
    assert r["templates_flipped"] > 0 and r["wrong_verdicts"] > 0


def test_bf16_rounding_ties_to_even():
    x = np.asarray([1.0, 1.00390625, 1.01171875, -2.5, np.inf], np.float32)
    y = reference._bf16(x)
    np.testing.assert_array_equal(
        y, np.asarray([1.0, 1.0, 1.015625, -2.5, np.inf], np.float32))
    np.testing.assert_array_equal(reference._bf16(y), y)
