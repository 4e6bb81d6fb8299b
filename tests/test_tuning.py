"""Cost-model routing + cached autotuning (repro.tuning).

Two families of properties:

  * the ROUTER is sane — estimates scale the right way (monotone in B,
    pallas padding grows with S), fitted coefficients reproduce
    synthetic timings, the cache round-trips and survives corruption;
  * the ROUTE is invisible — ``impl="auto"`` and ``impl="tuned"``
    produce verdicts bit-identical to the backend they resolve to, for
    single batches and for streaming, because routing is a pure
    execution choice (docs/PARITY.md).
"""
import json
import os

import numpy as np
import pytest

from repro.core.inference import (
    Engine,
    PALLAS_BACKEND,
    backend_for_plan,
    get_backend,
    pallas_backend,
)
from repro.flows.windows import window_packets
from repro.serve.streaming import run_streaming
from repro.tuning import (
    Coefficients,
    Plan,
    ShapeInfo,
    choose_plan,
    estimate_us,
    fit_coefficients,
    work_terms,
)
from repro.tuning.autotune import (
    CACHE_ENV,
    NO_TIME_ENV,
    autotune,
    cache_key,
    device_fingerprint,
    load_cache,
    save_cache,
)
from repro.core.inference import EngineOptions


def _shape(B=1024, S=9, k=4, P=3, W=32, T=8, L=16, **kw):
    return ShapeInfo(B=B, S=S, k=k, P=P, W=W, T=T, L=L, **kw)


@pytest.fixture()
def tune_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv(CACHE_ENV, path)
    return path


@pytest.fixture(scope="module")
def tuned_engine(trained_pdt):
    pdt, Xw, tr = trained_pdt
    wp = window_packets(tr, 3)
    return Engine.from_model(pdt), wp, pdt, Xw


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------
def test_shape_from_engine(tuned_engine):
    eng, wp, pdt, _ = tuned_engine
    s = ShapeInfo.from_engine(eng, wp)
    assert s.B == wp.shape[0] and s.W == wp.shape[2]
    assert s.S == eng.ret.n_subtrees and s.k == eng.ret.k
    assert s.P == eng.tables.n_partitions
    assert s.key() == ShapeInfo.from_engine(eng, wp).key()


def test_shape_validation():
    with pytest.raises(ValueError, match="must be positive"):
        _shape(S=0)
    with pytest.raises(ValueError, match="survivors"):
        _shape(survivors=(1.0, 0.5))       # P=3 needs 3 entries
    with pytest.raises(ValueError, match="unknown backend"):
        Plan(backend="tofino")


@pytest.mark.parametrize("backend", ["looped", "fused", "pallas"])
def test_estimates_monotone_in_batch(backend):
    costs = [estimate_us(_shape(B=B), Plan(backend=backend))
             for B in (128, 1024, 8192)]
    assert costs == sorted(costs)
    assert costs[0] > 0


def test_pallas_estimate_grows_with_subtrees():
    """The capacity bound ceil(B/bb) + S charges pallas for per-subtree
    padding; dense fused work is S-independent (gathers are per-flow)."""
    pal = [estimate_us(_shape(S=S), Plan(backend="pallas"))
           for S in (2, 16, 64)]
    assert pal == sorted(pal) and pal[0] < pal[-1]
    fus = [estimate_us(_shape(S=S), Plan(backend="fused"))
           for S in (2, 16, 64)]
    assert fus[0] == pytest.approx(fus[-1])


def test_compact_work_tracks_survivors():
    """With front-loaded exits the compacted plan does less work than
    the dense one; with no survivor info compaction is pure overhead."""
    surv = (1.0, 0.1, 0.05)
    dense = estimate_us(_shape(survivors=surv), Plan(backend="fused"))
    comp = estimate_us(_shape(survivors=surv),
                       Plan(backend="fused", compact=True))
    assert comp < dense
    no_info = estimate_us(_shape(), Plan(backend="fused", compact=True))
    assert no_info >= estimate_us(_shape(), Plan(backend="fused"))


def test_choose_plan_restricted_backends():
    for b in ("looped", "fused", "pallas"):
        assert choose_plan(_shape(), backends=(b,)).backend == b
    plan = choose_plan(_shape())
    assert plan.source == "costmodel" and plan.est_us > 0


def test_default_coefficients_route_sanely():
    """On CPU the fitted defaults must route every realistic shape to
    the fused walk (interpret-mode pallas and the host loop lose)."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("CPU-fitted defaults under test")
    for B in (256, 2048, 65536):
        for S in (4, 32):
            assert choose_plan(_shape(B=B, S=S)).backend == "fused"


def test_default_coefficients_reject_unknown_platform(monkeypatch):
    """A platform with no coefficient row is an error, never a silent
    fallback to another platform's weights."""
    import jax
    from repro.tuning.costmodel import default_coefficients
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="gpu"):
        default_coefficients("fused")


def test_fit_coefficients_recovers_synthetic_weights():
    """Generate timings from known weights; the NNLS fit must recover
    them (and estimates must reproduce the synthetic timings)."""
    true = Coefficients(call=500.0, sync=0.0, fw=1e-3, tr_dense=2e-3,
                        tr_pallas=0.0, grid=0.0, sort=0.0)
    # vary W and L independently of B so the feature-window and
    # traversal columns are not collinear (both scale with B)
    shapes = [_shape(B=B, W=W, L=L)
              for B in (128, 512, 4096) for W, L in ((16, 8), (64, 32))]
    samples = [(s, Plan(backend="fused"),
                float(work_terms(s, Plan(backend="fused")) @ true.vector()))
               for s in shapes]
    fit = fit_coefficients(samples)
    for s, p, us in samples:
        assert estimate_us(s, p, fit) == pytest.approx(us, rel=1e-6)
    assert fit.fw == pytest.approx(1e-3, rel=1e-3)
    assert fit.tr_dense == pytest.approx(2e-3, rel=1e-3)


def test_fit_keeps_base_for_unsupported_terms():
    base = Coefficients(call=1.0, sync=99.0, fw=1.0, tr_dense=1.0,
                        tr_pallas=77.0, grid=88.0, sort=1.0)
    s = _shape()
    us = float(work_terms(s, Plan(backend="fused")) @ base.vector())
    fit = fit_coefficients([(s, Plan(backend="fused"), us)], base=base)
    # fused samples exercise no pallas terms: base survives
    assert fit.tr_pallas == 77.0 and fit.grid == 88.0


# ---------------------------------------------------------------------------
# autotune cache
# ---------------------------------------------------------------------------
def test_cache_round_trip(tune_cache):
    entries = {"k1": {"backend": "fused", "block_b": 128, "compact": False,
                      "compact_floor": 128, "us": 12.5}}
    save_cache(entries, tune_cache)
    assert load_cache(tune_cache) == entries
    # corrupt file -> tolerated, treated as empty (tuning never breaks
    # inference)
    with open(tune_cache, "w") as f:
        f.write("{not json")
    assert load_cache(tune_cache) == {}
    # wrong version -> ignored
    with open(tune_cache, "w") as f:
        json.dump({"version": 999, "entries": entries}, f)
    assert load_cache(tune_cache) == {}
    assert load_cache(str(tune_cache) + ".does-not-exist") == {}


def test_cache_key_includes_device_and_shape():
    k1 = cache_key(_shape(B=256))
    k2 = cache_key(_shape(B=512))
    assert k1 != k2
    assert device_fingerprint() in k1
    assert cache_key(_shape(B=256), streaming=True) != k1
    # pinned compact requests must not be served a compact="auto" plan
    # (and vice versa): they tune and cache separately
    assert len({cache_key(_shape(B=256), compact=c)
                for c in ("auto", True, False)}) == 3


def test_cached_auto_plan_does_not_override_pinned_compact(
        tuned_engine, tune_cache):
    eng, wp, _, _ = tuned_engine
    free = autotune(eng, wp, backends=("fused",), compact="auto",
                    repeat=1, probe_flows=64)
    assert free.source == "timed"
    pinned = autotune(eng, wp, backends=("fused",), compact=False,
                      repeat=1, probe_flows=64)
    # a fresh (pinned) tuning run, not a cache hit on the "auto" entry
    assert pinned.source == "timed" and pinned.compact is False
    assert autotune(eng, wp, backends=("fused",), compact=False,
                    repeat=1).source == "cache"


def test_autotune_times_caches_and_rehits(tuned_engine, tune_cache):
    eng, wp, _, _ = tuned_engine
    plan = autotune(eng, wp, backends=("fused",), compact=False,
                    repeat=1, probe_flows=64)
    assert plan.backend == "fused" and plan.source == "timed"
    assert os.path.exists(tune_cache)
    again = autotune(eng, wp, backends=("fused",), compact=False, repeat=1)
    assert again.source == "cache" and again.backend == "fused"
    forced = autotune(eng, wp, backends=("fused",), compact=False,
                      repeat=1, probe_flows=64, force=True)
    assert forced.source == "timed"


def test_autotune_no_timing_falls_back_to_costmodel(
        tuned_engine, tune_cache, monkeypatch):
    eng, wp, _, _ = tuned_engine
    monkeypatch.setenv(NO_TIME_ENV, "1")
    plan = autotune(eng, wp)
    assert plan.source == "costmodel"
    assert not os.path.exists(tune_cache)    # nothing was persisted


# ---------------------------------------------------------------------------
# routing parity: auto / tuned are invisible (zero tolerance)
# ---------------------------------------------------------------------------
def _assert_identical(a, b):
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.recircs, b.recircs)
    np.testing.assert_array_equal(a.exit_partition, b.exit_partition)


def test_auto_impl_bit_identical_and_emits_plan(tuned_engine):
    eng, wp, pdt, Xw = tuned_engine
    auto = eng.run(wp, with_trace=False, options=EngineOptions(impl="auto"))
    assert auto.plan is not None and auto.plan.source == "costmodel"
    forced = eng.run(wp, with_trace=False, options=EngineOptions(impl=auto.plan.backend))
    assert forced.plan is None               # forced impls carry no plan
    _assert_identical(auto, forced)
    # ... and to the offline oracle
    labels, recircs, exit_p = pdt.predict(Xw, return_trace=True)
    np.testing.assert_array_equal(auto.labels, labels)
    np.testing.assert_array_equal(auto.recircs, recircs)
    np.testing.assert_array_equal(auto.exit_partition, exit_p)


def test_tuned_impl_bit_identical_to_routed_backend(tuned_engine,
                                                    tune_cache):
    eng, wp, _, _ = tuned_engine
    tuned = eng.run(wp, with_trace=False, options=EngineOptions(impl="tuned"))
    assert tuned.plan is not None and tuned.plan.source == "timed"
    again = eng.run(wp, with_trace=False, options=EngineOptions(impl="tuned"))
    assert again.plan.source == "cache"
    assert again.plan.backend == tuned.plan.backend
    # splint: allow[R005]: ExecutionBackend protocol run() — compact is a
    # real parameter here, not the Engine deprecation shim
    forced = backend_for_plan(again.plan).run(
        eng, wp, with_trace=False, compact=again.plan.compact,
        compact_floor=again.plan.compact_floor)
    _assert_identical(again, forced)
    _assert_identical(again, tuned)


def test_compact_auto_resolves_via_plan(tuned_engine):
    eng, wp, _, _ = tuned_engine
    res = eng.run(wp, with_trace=False, options=EngineOptions(impl="fused", compact="auto"))
    assert res.plan is not None and res.plan.backend == "fused"
    _assert_identical(res, eng.run(wp, with_trace=False, options=EngineOptions(impl="fused")))


def test_streaming_auto_and_tuned_parity(tuned_engine, tune_cache):
    eng, wp, _, _ = tuned_engine
    full = eng.run(wp, with_trace=False, options=EngineOptions(impl="fused"))
    auto = run_streaming(eng, wp, options=EngineOptions(micro_batch=96, impl="auto"))
    assert auto.plan is not None
    assert auto.plan.backend in ("fused", "pallas")   # walk backends only
    _assert_identical(auto, full)
    tuned = run_streaming(eng, wp, options=EngineOptions(micro_batch=96, impl="tuned"))
    assert tuned.plan is not None
    _assert_identical(tuned, full)
    # fixed impl: no plan attached
    assert run_streaming(eng, wp, options=EngineOptions(micro_batch=96, impl="fused")).plan is None


def test_custom_block_b_backend_bit_identical(tuned_engine):
    """block_b is a pure layout knob: any block size must reproduce the
    default walk bit-for-bit (registers included)."""
    eng, wp, _, _ = tuned_engine
    assert pallas_backend(128) is PALLAS_BACKEND
    ref = eng.run(wp[:96], with_trace=True, options=EngineOptions(impl="fused"))
    for bb in (32, 64):
        res = pallas_backend(bb).run(eng, wp[:96], with_trace=True)
        _assert_identical(res, ref)
        for a, b in zip(res.regs_trace, ref.regs_trace):
            np.testing.assert_array_equal(a, b)


def test_compact_floor_bit_identical(tuned_engine):
    eng, wp, _, _ = tuned_engine
    dense = eng.run(wp, with_trace=False, options=EngineOptions(impl="fused"))
    for floor in (32, 256):
        # splint: allow[R005]: ExecutionBackend protocol run() —
        # compact/compact_floor are real parameters here, not the shim
        res = backend_for_plan(
            Plan(backend="fused", compact=True, compact_floor=floor)).run(
                eng, wp, with_trace=False, compact=True,
                compact_floor=floor)
        _assert_identical(res, dense)


def test_get_backend_rejects_tuned_without_engine():
    with pytest.raises(ValueError, match="shape-dependent"):
        get_backend("tuned")


def test_get_backend_auto_with_shape_uses_cost_model():
    import jax
    backend = get_backend("auto", shape=_shape(B=2048))
    if jax.default_backend() != "tpu":
        assert backend.name == "fused"
    assert backend.step is not None
