"""Streaming scheduler: chunked + padded micro-batches over the fused
engine must be indistinguishable from one full-batch run, for every
chunking — including ragged tails and chunks larger than the batch
(the padding-leak invariant of docs/PARITY.md)."""
import numpy as np
import pytest

from repro.core.inference import Engine
from repro.core.partition import train_partitioned_dt
from repro.flows.synthetic import make_dataset
from repro.flows.windows import window_features, window_packets
from repro.serve.streaming import microbatches, run_streaming, stream_batches
from repro.testing.hypothesis_compat import given, settings, strategies as st
from repro.core.inference import EngineOptions


@pytest.fixture(scope="module")
def stream_setup(trained_pdt):
    pdt, Xw, tr = trained_pdt
    wp = window_packets(tr, 3)
    eng = Engine.from_model(pdt)
    full = eng.run(wp, with_trace=False)
    oracle = pdt.predict(Xw, return_trace=True)
    return eng, wp, full, oracle


def _assert_same(res, full):
    np.testing.assert_array_equal(res.labels, full.labels)
    np.testing.assert_array_equal(res.recircs, full.recircs)
    np.testing.assert_array_equal(res.exit_partition, full.exit_partition)


def test_microbatch_bounds_cover_exactly():
    bounds = list(microbatches(103, 32))
    assert bounds == [(0, 32), (32, 64), (64, 96), (96, 103)]
    assert list(microbatches(32, 32)) == [(0, 32)]
    with pytest.raises(ValueError):
        list(microbatches(10, 0))


@pytest.mark.parametrize("micro_batch", [1, 7, 64, 10_000])
def test_streaming_equals_full_batch(stream_setup, micro_batch):
    """Every chunking — single-flow, ragged tail, one giant chunk —
    reproduces the full-batch fused run exactly."""
    eng, wp, full, _ = stream_setup
    res = run_streaming(eng, wp, options=EngineOptions(micro_batch=micro_batch))
    _assert_same(res, full)


def test_streaming_matches_oracle(stream_setup):
    """End-to-end: chunked streaming still equals the numpy oracle
    (labels AND recirculation counts — the bandwidth model's input)."""
    eng, wp, _, (labels, recircs, exit_p) = stream_setup
    res = eng.run_streaming(wp, options=EngineOptions(micro_batch=50))
    np.testing.assert_array_equal(res.labels, labels)
    np.testing.assert_array_equal(res.recircs, recircs)
    np.testing.assert_array_equal(res.exit_partition, exit_p)


def test_streaming_padded_tail_is_isolated(stream_setup):
    """A ragged tail is padded with invalid packets; padding must never
    leak into real flows' verdicts (micro_batch chosen so the last
    chunk is mostly padding)."""
    eng, wp, full, _ = stream_setup
    B = wp.shape[0]
    mb = B - 1            # tail chunk holds exactly 1 real flow
    res = run_streaming(eng, wp, options=EngineOptions(micro_batch=mb))
    _assert_same(res, full)


def test_stream_batches_generator(stream_setup):
    """Open-stream form: per-batch results concatenate to the full run."""
    eng, wp, full, _ = stream_setup
    cuts = [0, 13, 200, wp.shape[0]]
    parts = [wp[a:b] for a, b in zip(cuts, cuts[1:])]
    outs = list(stream_batches(eng, parts, options=EngineOptions(micro_batch=64)))
    assert len(outs) == len(parts)
    labels = np.concatenate([o.labels for o in outs])
    recircs = np.concatenate([o.recircs for o in outs])
    np.testing.assert_array_equal(labels, full.labels)
    np.testing.assert_array_equal(recircs, full.recircs)


def test_streaming_donate_flag_explicit(stream_setup):
    """There is no donation knob: no output of the walk can reuse the
    packet buffer on any backend, so asking for one is an error rather
    than a silent no-op — and the stream stays exact without it."""
    eng, wp, full, _ = stream_setup
    with pytest.raises(TypeError):
        EngineOptions(donate=True)
    with pytest.raises(TypeError):
        run_streaming(eng, wp, donate=False)
    res = run_streaming(eng, wp, options=EngineOptions(micro_batch=33))
    _assert_same(res, full)


@pytest.mark.parametrize("inflight", [1, 3, 8])
def test_streaming_pipelining_depth(stream_setup, inflight):
    """Async in-flight dispatch (any depth) must not change verdicts —
    chunks complete out of the host loop but land in the right rows."""
    eng, wp, full, _ = stream_setup
    res = run_streaming(eng, wp, options=EngineOptions(micro_batch=40, inflight=inflight))
    _assert_same(res, full)
    with pytest.raises(ValueError):
        run_streaming(eng, wp, options=EngineOptions(inflight=0))


def test_streaming_pallas_backend(stream_setup):
    """The in-jit SID dispatch makes the Pallas walk streamable (the
    host-grouped PR 1 path had to reject this); verdicts identical."""
    eng, wp, full, _ = stream_setup
    res = run_streaming(eng, wp[:96], options=EngineOptions(micro_batch=32, impl="pallas"))
    np.testing.assert_array_equal(res.labels, full.labels[:96])
    np.testing.assert_array_equal(res.recircs, full.recircs[:96])
    np.testing.assert_array_equal(res.exit_partition, full.exit_partition[:96])


def test_streaming_rejects_looped_backend(stream_setup):
    eng, wp, _, _ = stream_setup
    with pytest.raises(ValueError, match="walk backend"):
        run_streaming(eng, wp, options=EngineOptions(impl="looped"))


@pytest.mark.parametrize("micro_batch", [40, 10_000])
def test_streaming_compact_equals_full_batch(stream_setup, micro_batch):
    """Early-exit compaction inside each chunk's walk (including the
    padded ragged tail, whose padding rows all 'exit' immediately and
    get compacted away) must not change a single verdict."""
    eng, wp, full, _ = stream_setup
    res = run_streaming(eng, wp, options=EngineOptions(micro_batch=micro_batch, compact=True))
    _assert_same(res, full)


def test_streaming_compact_pallas(stream_setup):
    eng, wp, full, _ = stream_setup
    res = run_streaming(eng, wp[:96], options=EngineOptions(micro_batch=32, impl="pallas", compact=True))
    np.testing.assert_array_equal(res.labels, full.labels[:96])
    np.testing.assert_array_equal(res.recircs, full.recircs[:96])
    np.testing.assert_array_equal(res.exit_partition, full.exit_partition[:96])


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_streaming_padding_never_leaks_property(seed):
    """Adversarial-padding property: the zero rows the scheduler pads
    ragged tails with DECODE TO A VALID EXIT ACTION (an all-invalid
    window produces deterministic registers, and a trained subtree maps
    every register vector to some leaf), so any padding row that leaked
    into the result buffer would overwrite a real verdict with a
    plausible-looking class.  For random chunkings, pipelining depths,
    and compaction, results must equal the unpadded full-batch run."""
    rng = np.random.default_rng(seed)
    ds = make_dataset("d2", n_flows=160, seed=seed)
    Xw = window_features(ds, 2)
    pdt = train_partitioned_dt(Xw, ds.labels,
                               partition_sizes=[2, 2], k=3)
    wp = window_packets(ds, 2)
    eng = Engine.from_model(pdt)
    full = eng.run(wp, with_trace=False)
    # the adversarial premise: all-zero "padding" flows really do decode
    # to valid verdicts (no -1s) — i.e. padding is indistinguishable
    # from a confident classification if it ever leaks
    zero = eng.run(np.zeros_like(wp[:8]), with_trace=False)
    assert (zero.labels >= 0).all()
    B = wp.shape[0]
    for _ in range(3):
        mb = int(rng.integers(1, B + 40))
        res = run_streaming(eng, wp, options=EngineOptions(micro_batch=mb, inflight=int(rng.integers(1, 4)), compact=bool(rng.integers(0, 2))))
        np.testing.assert_array_equal(res.labels, full.labels)
        np.testing.assert_array_equal(res.recircs, full.recircs)
        np.testing.assert_array_equal(res.exit_partition,
                                      full.exit_partition)
