"""Serving subsystem (mxnet_tpu/serving/): bucketed AOT program cache,
dynamic micro-batcher, InferenceEngine facade, and the integration points
(Executor.warmup AOT path, Module.predict routing, engine bulk knob,
MXNET_TPU_COMPILE_CACHE).

The two contracts the ISSUE names explicitly:
  * padding correctness — engine outputs for a batch of N equal the
    unbatched executor outputs row-for-row (rtol 1e-5) across every bucket
    boundary (N = bucket, bucket±1);
  * cache behavior — repeated predicts within one bucket trigger exactly
    one compile; a new bucket triggers exactly one more.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (InferenceEngine, DynamicBatcher,
                               BucketedProgramCache, DeadlineExceeded,
                               bucket_for, pad_to_bucket, default_max_batch)


def _net(with_bn=True):
    """MLP with BatchNorm (aux running stats) + Dropout (inference
    identity) — every per-row-independence claim the padding proof relies
    on gets exercised."""
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    if with_bn:
        net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Dropout(net, p=0.5)
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _params_for(sym, batch, rng):
    arg_shapes, _, aux_shapes = sym.infer_shape(data=(batch, 6))
    args = {n: mx.nd.array(rng.normal(0, 1, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    aux = {n: mx.nd.array(np.ones(s, np.float32) if "var" in n
                          else np.zeros(s, np.float32))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    return args, aux


def _executor_reference(sym, args, aux, x):
    """Unbatched/unpadded ground truth: bind at exactly x's batch size."""
    n = x.shape[0]
    exe = sym.simple_bind(mx.cpu(), grad_req="null", data=(n, 6),
                          softmax_label=(n,))
    for name, arr in args.items():
        arr.copyto(exe.arg_dict[name])
    for name, arr in aux.items():
        arr.copyto(exe.aux_dict[name])
    return exe.forward(is_train=False, data=mx.nd.array(x))[0].asnumpy()


# ---------------------------------------------------------------------------
# padding correctness (ISSUE acceptance: every bucket boundary)
# ---------------------------------------------------------------------------

def test_padding_correctness_across_bucket_boundaries():
    rng = np.random.RandomState(0)
    sym = _net()
    args, aux = _params_for(sym, 8, rng)
    buckets = (2, 4, 8)
    eng = InferenceEngine(sym, args, aux, ctx=mx.cpu(), buckets=buckets)
    # N = bucket, bucket±1 for every bucket — including N=9 > max bucket
    # (exact-shape program) and N=1 < min bucket (pads up to 2)
    sizes = sorted({max(1, b + d) for b in buckets for d in (-1, 0, 1)})
    for n in sizes:
        x = rng.normal(0, 1, (n, 6)).astype(np.float32)
        out = eng.predict({"data": x})[0].asnumpy()
        ref = _executor_reference(sym, args, aux, x)
        assert out.shape == ref.shape == (n, 3)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg="batch %d" % n)


def test_single_array_and_list_requests():
    rng = np.random.RandomState(1)
    sym = _net(with_bn=False)
    args, _ = _params_for(sym, 4, rng)
    eng = InferenceEngine(sym, args, {}, ctx=mx.cpu(), buckets=(4,))
    x = rng.normal(0, 1, (3, 6)).astype(np.float32)
    a = eng.predict(x)[0].asnumpy()              # bare array -> first input
    b = eng.predict({"data": x})[0].asnumpy()
    np.testing.assert_allclose(a, b, rtol=1e-6)
    with pytest.raises(MXNetError):
        eng.predict({"nonsense": x})


# ---------------------------------------------------------------------------
# cache behavior (ISSUE acceptance: zero recompilation within a bucket)
# ---------------------------------------------------------------------------

def test_compile_counter_one_compile_per_bucket():
    rng = np.random.RandomState(2)
    sym = _net()
    args, aux = _params_for(sym, 8, rng)
    eng = InferenceEngine(sym, args, aux, ctx=mx.cpu(), buckets=(4, 8))
    x = rng.normal(0, 1, (3, 6)).astype(np.float32)
    for _ in range(4):                       # N=3 -> bucket 4, one compile
        eng.predict({"data": x})
    assert eng.compiles == 1
    eng.predict({"data": x[:2]})             # N=2 -> same bucket: no compile
    eng.predict({"data": np.concatenate([x, x])[:4]})  # N=4: same bucket
    assert eng.compiles == 1
    assert eng.misses == 1 and eng.hits == 5
    eng.predict({"data": np.concatenate([x, x])})      # N=6 -> bucket 8
    assert eng.compiles == 2
    eng.predict({"data": np.concatenate([x, x])[:5]})  # N=5: cached bucket 8
    assert eng.compiles == 2


def test_warmup_precompiles_every_bucket():
    rng = np.random.RandomState(3)
    sym = _net()
    args, aux = _params_for(sym, 8, rng)
    eng = InferenceEngine(sym, args, aux, ctx=mx.cpu(), buckets=(2, 4, 8))
    assert eng.warmup({"data": (8, 6)}) == 3
    assert eng.compiles == 3
    for n in (1, 2, 3, 5, 8):
        eng.predict({"data": rng.normal(0, 1, (n, 6)).astype(np.float32)})
    assert eng.compiles == 3 and eng.misses == 0 and eng.hits == 5


def test_update_params_no_recompile():
    rng = np.random.RandomState(4)
    sym = _net(with_bn=False)
    args, _ = _params_for(sym, 4, rng)
    eng = InferenceEngine(sym, args, {}, ctx=mx.cpu(), buckets=(4,))
    x = rng.normal(0, 1, (4, 6)).astype(np.float32)
    out1 = eng.predict({"data": x})[0].asnumpy()
    new_args = {n: mx.nd.array(rng.normal(0, 1, a.shape).astype(np.float32))
                for n, a in args.items()}
    eng.update_params(new_args)
    out2 = eng.predict({"data": x})[0].asnumpy()
    assert eng.compiles == 1                 # params are runtime args
    assert not np.allclose(out1, out2)       # ...but the values did change
    np.testing.assert_allclose(
        out2, _executor_reference(sym, new_args, {}, x), rtol=1e-5,
        atol=1e-6)


def test_bucket_for_contract():
    assert bucket_for(1, (4, 8)) == 4
    assert bucket_for(4, (4, 8)) == 4
    assert bucket_for(5, (4, 8)) == 8
    assert bucket_for(9, (4, 8)) == 9        # oversized: exact shape
    with pytest.raises(MXNetError):
        bucket_for(0, (4, 8))


# ---------------------------------------------------------------------------
# dynamic batcher
# ---------------------------------------------------------------------------

def test_batcher_coalesces_pads_and_splits():
    calls = []

    def run_batch(padded, n_real):
        calls.append((padded["x"].shape[0], n_real))
        return [padded["x"] * 2.0]

    b = DynamicBatcher(run_batch, buckets=(4,), max_batch=4,
                       autostart=False)
    reqs = [b.submit({"x": np.full((1, 2), i, np.float32)})
            for i in range(5)]
    assert not any(r.done() for r in reqs)
    b.flush()                                # deterministic: calling thread
    # 5 single-row requests, cap 4 -> one full batch + one padded remainder
    assert calls == [(4, 4), (4, 1)]
    for i, r in enumerate(reqs):
        out = r.result_wait(1.0)
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.full((1, 2), 2.0 * i))
    st = b.stats()
    assert st["batches_run"] == 2 and st["padded_rows"] == 3
    assert st["rows"] == 5 and st["requests"] == 5


def test_batcher_fill_scan_beats_fifo_prefix():
    calls = []

    def run_batch(padded, n_real):
        calls.append(padded["x"].shape[0])
        return [padded["x"]]

    b = DynamicBatcher(run_batch, buckets=(8,), max_batch=8,
                       autostart=False)
    for n in (6, 3, 2):   # FIFO prefix alone would dispatch 6 then 3+2
        b.submit({"x": np.zeros((n, 1), np.float32)})
    b.flush()
    # fill scan packs 6+2 into one bucket, then 3 pads into the next
    assert b.stats()["batches_run"] == 2
    assert b.stats()["padded_rows"] == (8 - 8) + (8 - 3)


def test_batcher_error_propagates_to_every_waiter():
    def run_batch(padded, n_real):
        raise RuntimeError("chip fell over")

    b = DynamicBatcher(run_batch, buckets=(4,), max_batch=4,
                       autostart=False)
    reqs = [b.submit({"x": np.zeros((1, 1), np.float32)}) for _ in range(2)]
    b.flush()
    for r in reqs:
        with pytest.raises(MXNetError, match="chip fell over"):
            r.result_wait(1.0)


def test_batcher_oversized_dispatches_solo_and_mismatched_rejects():
    calls = []

    def run_batch(padded, n_real):
        calls.append(padded["x"].shape[0])
        return [padded["x"]]

    b = DynamicBatcher(run_batch, buckets=(4,), max_batch=4,
                       autostart=False)
    # a request above max_batch is not rejected: the cap bounds
    # COALESCING, not request size (sync predict has no cap either)
    r = b.submit({"x": np.arange(5, dtype=np.float32).reshape(5, 1)})
    b.flush()
    assert calls == [5]                      # solo, exact-shape bucket
    np.testing.assert_allclose(np.asarray(r.result_wait(1.0)[0]),
                               np.arange(5, dtype=np.float32).reshape(5, 1))
    with pytest.raises(MXNetError):
        b.submit({"x": np.zeros((2, 1), np.float32),
                  "y": np.zeros((3, 1), np.float32)})


def test_pad_to_bucket_replicates_row0():
    arrs = {"x": np.arange(6, dtype=np.float32).reshape(3, 2)}
    padded = pad_to_bucket(arrs, 3, 5)
    assert padded["x"].shape == (5, 2)
    np.testing.assert_allclose(padded["x"][3:], np.tile(arrs["x"][0], (2, 1)))
    assert pad_to_bucket(arrs, 3, 3) is arrs  # no copy when exact


def test_async_predict_matches_sync():
    rng = np.random.RandomState(5)
    sym = _net()
    args, aux = _params_for(sym, 8, rng)
    eng = InferenceEngine(sym, args, aux, ctx=mx.cpu(), buckets=(2, 4, 8),
                          max_delay_ms=1.0)
    xs = [rng.normal(0, 1, (n, 6)).astype(np.float32) for n in (1, 2, 3, 1)]
    futs = [eng.predict_async({"data": x}) for x in xs]
    for x, f in zip(xs, futs):
        out = f.result_wait(30.0)
        ref = eng.predict({"data": x})[0].asnumpy()
        np.testing.assert_allclose(np.asarray(out[0]), ref, rtol=1e-5,
                                   atol=1e-6)
    eng.stop()


# ---------------------------------------------------------------------------
# engine bulk knob (satellite: non-advisory set_bulk_size)
# ---------------------------------------------------------------------------

def test_set_bulk_size_validates_and_keeps_contract():
    prev = mx.engine.set_bulk_size(0)
    try:
        assert mx.engine.set_bulk_size(7) == 0
        assert mx.engine.set_bulk_size(0) == 7     # return-previous contract
        with pytest.raises(ValueError):
            mx.engine.set_bulk_size(-1)
        assert mx.engine.current_bulk_size() == 0  # failed set didn't stick
    finally:
        mx.engine.set_bulk_size(prev)


def test_max_batch_clamps_to_top_bucket():
    # a cap above the top bucket would coalesce to arbitrary totals, each
    # compiling a fresh exact-shape program — the batcher clamps instead
    b = DynamicBatcher(lambda p, n: [p["x"]], buckets=(2, 4, 8),
                       max_batch=64, autostart=False)
    assert b.max_batch == 8
    with mx.engine.bulk(64):
        b2 = DynamicBatcher(lambda p, n: [p["x"]], buckets=(2, 4, 8),
                            autostart=False)
        assert b2.max_batch == 8


def test_module_predict_falls_back_on_serve_incompatible_input():
    # second bound input with no batch axis: the engine only learns this
    # at dispatch (batch-size disagreement) — predict must fall back to
    # the executor sweep, not raise
    rng = np.random.RandomState(12)
    data = mx.sym.Variable("data")
    scale = mx.sym.Variable("scale")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc1")
    net = mx.sym.broadcast_mul(net, scale)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, data_names=("data", "scale"),
                        context=mx.cpu())
    X = rng.normal(0, 1, (8, 6)).astype(np.float32)
    S = np.full((1, 3), 2.0, np.float32)
    batches = [mx.io.DataBatch(
        data=[mx.nd.array(X[i:i + 4]), mx.nd.array(S)], label=[], pad=0)
        for i in (0, 4)]

    class _TwoBatchIter:
        def __init__(self):
            self.provide_data = [("data", (4, 6)), ("scale", (1, 3))]
            self.provide_label = []

        def reset(self):
            pass

        def __iter__(self):
            return iter(batches)

    mod.bind(data_shapes=_TwoBatchIter().provide_data, label_shapes=None,
             for_training=False)
    mod.init_params(mx.init.Uniform(0.1))
    preds = mod.predict(_TwoBatchIter())
    assert mod._serving_engine is None       # engine disabled itself
    assert preds.shape == (8, 3)


def test_bulk_size_feeds_batcher_max_batch():
    prev = mx.engine.set_bulk_size(0)
    try:
        assert default_max_batch((2, 4, 8)) == 8   # 0 -> largest bucket
        with mx.engine.bulk(6):
            assert default_max_batch((2, 4, 8)) == 6
            b = DynamicBatcher(lambda p, n: [p["x"]], buckets=(2, 4, 8),
                               autostart=False)
            assert b.max_batch == 6
        assert default_max_batch((2, 4, 8)) == 8
    finally:
        mx.engine.set_bulk_size(prev)


# ---------------------------------------------------------------------------
# integration: Executor.warmup AOT, Module.predict routing, gluon blocks
# ---------------------------------------------------------------------------

def test_executor_warmup_aot_matches_jit_path():
    rng = np.random.RandomState(6)
    sym = _net()
    exe = sym.simple_bind(mx.cpu(), grad_req="null", data=(4, 6),
                          softmax_label=(4,))
    for n, a in exe.arg_dict.items():
        if n not in ("data", "softmax_label"):
            a[:] = rng.normal(0, 1, a.shape).astype(np.float32)
    exe.aux_dict["bn1_moving_var"][:] = 1.0
    assert exe.warmup() is exe \
        and exe._fwd_fn(False).program_count() == 1
    exe.warmup()                             # idempotent: no second program
    assert exe._fwd_fn(False).program_count() == 1
    x = mx.nd.array(rng.normal(0, 1, (4, 6)).astype(np.float32))
    out = exe.forward(is_train=False, data=x)[0].asnumpy()
    exe2 = sym.simple_bind(mx.cpu(), grad_req="null", data=(4, 6),
                           softmax_label=(4,))
    for n, a in exe.arg_dict.items():
        a.copyto(exe2.arg_dict[n])
    for n, a in exe.aux_dict.items():
        a.copyto(exe2.aux_dict[n])
    ref = exe2.forward(is_train=False, data=x)[0].asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_module_predict_routes_through_serving_engine(monkeypatch):
    rng = np.random.RandomState(7)
    X = rng.normal(0, 1, (26, 6)).astype(np.float32)  # 26 = 2*10 + 6 (pad)
    sym = _net(with_bn=False)
    mod = mx.mod.Module(sym, context=mx.cpu())
    it = mx.io.NDArrayIter(X, None, batch_size=10)
    mod.bind(data_shapes=it.provide_data, label_shapes=None,
             for_training=False)
    mod.init_params(mx.init.Uniform(0.1))
    preds = mod.predict(it)
    assert mod._serving_engine is not None   # engine path was taken
    assert mod._serving_engine.compiles == 1  # full + padded batches share
    assert preds.shape == (26, 3)             # one bucket-10 program
    monkeypatch.setenv("MXNET_SERVING_PREDICT", "0")
    ref = mod.predict(it)                     # plain executor sweep
    np.testing.assert_allclose(preds.asnumpy(), ref.asnumpy(), rtol=1e-5,
                               atol=1e-6)


def test_module_predict_with_labels_matches_executor_path(monkeypatch):
    rng = np.random.RandomState(8)
    X = rng.normal(0, 1, (20, 6)).astype(np.float32)
    y = rng.randint(0, 3, (20,)).astype(np.float32)
    mod = mx.mod.Module(_net(), context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=8, label_name="softmax_label")
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Uniform(0.1))
    preds = mod.predict(it)
    monkeypatch.setenv("MXNET_SERVING_PREDICT", "0")
    ref = mod.predict(it)
    np.testing.assert_allclose(preds.asnumpy(), ref.asnumpy(), rtol=1e-5,
                               atol=1e-6)


def test_engine_on_non_default_device():
    # the AOT programs must compile FOR the engine's device: lowering
    # from abstract shapes otherwise pins the default device and every
    # predict dies on a committed-device mismatch (8-device CPU mesh)
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    rng = np.random.RandomState(13)
    sym = _net(with_bn=False)
    args, _ = _params_for(sym, 4, rng)
    eng = InferenceEngine(sym, args, {}, ctx=mx.cpu(1), buckets=(4,))
    eng.warmup({"data": (4, 6)})
    x = rng.normal(0, 1, (3, 6)).astype(np.float32)
    out = eng.predict({"data": x})[0]
    np.testing.assert_allclose(out.asnumpy(),
                               _executor_reference(sym, args, {}, x),
                               rtol=1e-5, atol=1e-6)


def test_predict_device_resident_inputs_stay_on_device():
    rng = np.random.RandomState(14)
    sym = _net(with_bn=False)
    args, _ = _params_for(sym, 4, rng)
    eng = InferenceEngine(sym, args, {}, ctx=mx.cpu(), buckets=(4,))
    x = rng.normal(0, 1, (4, 6)).astype(np.float32)
    xd = mx.nd.array(x)                      # device-resident request
    out = eng.predict({"data": xd})[0].asnumpy()
    np.testing.assert_allclose(out, eng.predict({"data": x})[0].asnumpy(),
                               rtol=1e-6)
    # exact-bucket device input must not be consumed/corrupted
    np.testing.assert_allclose(xd.asnumpy(), x, rtol=0)


def test_engine_from_hybrid_block():
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Uniform(0.1))
    x = mx.nd.array(np.random.RandomState(9)
                    .normal(0, 1, (3, 6)).astype(np.float32))
    ref = net(x).asnumpy()
    eng = InferenceEngine.from_block(net, ctx=mx.cpu(), buckets=(4,))
    out = eng.predict({"data": x})[0].asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# MXNET_TPU_COMPILE_CACHE (satellite: base.py env wiring)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("placed_outside", [False, True])
def test_compile_cache_env_wiring(tmp_path, monkeypatch, placed_outside):
    """MXNET_TPU_COMPILE_CACHE places the cache only where nobody placed it
    from outside: with JAX_COMPILATION_CACHE_DIR set (jax reads it into
    jax_compilation_cache_dir) that directory is used and no other is set
    in code."""
    import jax
    from mxnet_tpu import base
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_state = dict(base._compile_cache_state)
    outside = str(tmp_path / "outside")
    try:
        jax.config.update("jax_compilation_cache_dir",
                          outside if placed_outside else None)
        base._compile_cache_state.update(configured=False, dir=None)
        monkeypatch.delenv("MXNET_TPU_COMPILE_CACHE", raising=False)
        # our variable unset: whatever is (not) there stays
        assert base.configure_compile_cache() == \
            (outside if placed_outside else None)
        base._compile_cache_state.update(configured=False, dir=None)
        monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path / "ours"))
        want = outside if placed_outside else str(tmp_path / "ours")
        assert base.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert base.compile_cache_dir() == want
        # idempotent: second call returns the cached answer
        assert base.configure_compile_cache() == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        base._compile_cache_state.clear()
        base._compile_cache_state.update(prev_state)


# ---------------------------------------------------------------------------
# tier-1 smoke (<5s) + slow mixed-trace throughput
# ---------------------------------------------------------------------------

def test_serving_smoke_fast():
    """<5s end-to-end: warmup -> sync predict -> async predict -> stats.
    The tier-1 stand-in for the slow mixed-trace test below."""
    tic = time.time()
    rng = np.random.RandomState(10)
    sym = _net(with_bn=False)
    args, _ = _params_for(sym, 4, rng)
    eng = InferenceEngine(sym, args, {}, ctx=mx.cpu(), buckets=(2, 4))
    eng.warmup({"data": (4, 6)})
    x = rng.normal(0, 1, (3, 6)).astype(np.float32)
    out = eng.predict({"data": x})[0]
    assert out.shape == (3, 3)
    fut = eng.predict_async({"data": x[:1]})
    np.testing.assert_allclose(np.asarray(fut.result_wait(10.0)[0]),
                               out.asnumpy()[:1], rtol=1e-5, atol=1e-6)
    st = eng.stats()
    assert st["compiles"] == 2 and st["requests"] == 1
    eng.stop()
    assert time.time() - tic < 5.0


@pytest.mark.slow
def test_mixed_trace_serving_throughput():
    """Mixed 1..8 batch-size trace through predict_async: every request's
    rows come back correct, coalescing actually happens (fewer executable
    calls than requests), and no program compiles beyond the warmed
    buckets."""
    rng = np.random.RandomState(11)
    sym = _net()
    args, aux = _params_for(sym, 8, rng)
    eng = InferenceEngine(sym, args, aux, ctx=mx.cpu(), buckets=(4, 8),
                          max_batch=8, max_delay_ms=5.0)
    eng.warmup({"data": (8, 6)})
    trace = [int(n) for n in rng.randint(1, 9, size=40)]
    xs = [rng.normal(0, 1, (n, 6)).astype(np.float32) for n in trace]
    tic = time.time()
    futs = [eng.predict_async({"data": x}) for x in xs]
    outs = [f.result_wait(60.0) for f in futs]
    dt = time.time() - tic
    st = eng.stats()
    assert st["compiles"] == 2               # warmed buckets only
    assert st["batches_run"] < len(trace)    # coalescing happened
    for x, out in zip(xs, outs):
        np.testing.assert_allclose(np.asarray(out[0]),
                                   _executor_reference(sym, args, aux, x),
                                   rtol=1e-5, atol=1e-6)
    eng.stop()
    total = sum(trace)
    assert total / max(dt, 1e-9) > 0         # throughput is reportable


# ---------------------------------------------------------------------------
# SLA-aware batching: deadlines, EDF formation, load shedding (ISSUE 8)
# ---------------------------------------------------------------------------

def test_batcher_sheds_expired_deadline():
    """A request whose queue wait consumed its deadline budget fast-fails
    with the typed DeadlineExceeded; deadline-less traffic is untouched,
    and served + shed sums to submitted."""
    calls = []

    def run_batch(padded, n_real):
        calls.append(padded["x"].shape[0])
        return [padded["x"]]

    b = DynamicBatcher(run_batch, buckets=(4,), autostart=False)
    doomed = b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=1.0)
    safe = b.submit({"x": np.ones((1, 1), np.float32)})
    time.sleep(0.02)                       # the 1 ms budget is now spent
    b.flush()
    with pytest.raises(DeadlineExceeded):
        doomed.result_wait(1.0)
    np.testing.assert_allclose(np.asarray(safe.result_wait(1.0)[0]), 1.0)
    st = b.stats()
    assert st["shed"] == 1 and st["served"] == 1
    assert st["served"] + st["shed"] == st["requests"] == 2
    assert calls == [4]                    # the shed request never ran


def test_batcher_submit_sheds_impossible_budget():
    """A deadline below the bucket's measured step time can never be met
    even on an idle engine — shed at submit, before queueing."""
    b = DynamicBatcher(lambda p, n: [p["x"]], buckets=(4,),
                       autostart=False, step_time=lambda bucket: 0.2)
    req = b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=50.0)
    assert req.done()                      # resolved without any dispatch
    with pytest.raises(DeadlineExceeded, match="below the bucket"):
        req.result_wait(0.0)
    assert b.stats()["shed"] == 1 and b.stats()["requests"] == 1
    assert not b._queue
    with pytest.raises(MXNetError):
        b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=0)


def test_batcher_edf_order_priority_above_deadline():
    """Batch formation is earliest-deadline-first; priority orders above
    EDF; deadline-less requests go last at equal priority (FIFO there)."""
    order = []

    def run_batch(padded, n_real):
        order.append(int(padded["x"][0, 0]))
        return [padded["x"]]

    b = DynamicBatcher(run_batch, buckets=(4,), max_batch=4,
                       autostart=False)
    # marker 0: late deadline; 1: early; 2: mid; 3: none; 4: low deadline
    # but HIGH priority -> dispatches first
    b.submit({"x": np.full((4, 1), 0, np.float32)}, deadline_ms=5000.0)
    b.submit({"x": np.full((4, 1), 1, np.float32)}, deadline_ms=1000.0)
    b.submit({"x": np.full((4, 1), 2, np.float32)}, deadline_ms=3000.0)
    b.submit({"x": np.full((4, 1), 3, np.float32)})
    b.submit({"x": np.full((4, 1), 4, np.float32)}, deadline_ms=8000.0,
             priority=1)
    b.flush()
    assert order == [4, 1, 2, 0, 3]


def test_batcher_early_dispatch_on_tight_slack():
    """The worker must NOT hold a partial batch for the full max_delay
    window when the most urgent queued deadline cannot afford it: the
    batch goes out as soon as slack shrinks to slack_factor x measured
    step time."""
    b = DynamicBatcher(lambda p, n: [p["x"]], buckets=(8,),
                       max_delay_ms=10000.0, step_time=lambda bucket: 0.01,
                       slack_factor=5.0)
    tic = time.monotonic()
    req = b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=500.0)
    out = req.result_wait(8.0)             # << the 10 s window
    elapsed = time.monotonic() - tic
    assert out is not None and elapsed < 8.0
    assert b.stats()["early_dispatches"] >= 1
    assert b.stats()["shed"] == 0
    b.stop()


def test_batcher_idle_wait_is_event_driven():
    """Satellite: the idle wait is woken ONLY by submit/stop — no timer
    churn. The pre-ISSUE-8 batcher woke every 100 ms forever while idle
    (a 10-wakeups/second floor); the counter proves that's gone."""
    b = DynamicBatcher(lambda p, n: [p["x"]], buckets=(4,),
                       max_delay_ms=0.0)
    b.start()
    time.sleep(0.5)                         # idle: zero wakeups allowed
    assert b.stats()["idle_wakeups"] == 0
    req = b.submit({"x": np.zeros((1, 1), np.float32)})
    req.result_wait(5.0)
    time.sleep(0.3)                         # idle again after serving
    wakes = b.stats()["idle_wakeups"]
    assert 1 <= wakes <= 3                  # the submit (+ maybe a spurious
    time.sleep(0.3)                         # notify) — but NOT a timer:
    assert b.stats()["idle_wakeups"] == wakes
    b.stop()


def test_batcher_concurrent_producers_stop_race():
    """Satellite stress: N producer threads submitting mixed sizes while
    stop() races. Every ACCEPTED request must resolve exactly once with
    its own rows (result, solo-dispatch, or shed); submissions after stop
    raise; nothing is silently dropped."""
    import threading

    def run_batch(padded, n_real):
        return [padded["x"] * 2.0]

    b = DynamicBatcher(run_batch, buckets=(8,), max_delay_ms=1.0)
    accepted, rejected = [], [0]
    lock = threading.Lock()
    rng = np.random.RandomState(21)
    sizes = [[int(s) for s in rng.randint(1, 6, size=25)] for _ in range(6)]

    def producer(my_sizes, seed):
        prng = np.random.RandomState(seed)
        for n in my_sizes:
            x = prng.uniform(1, 2, (n, 2)).astype(np.float32)
            try:
                fut = b.submit({"x": x})
            except MXNetError:
                with lock:
                    rejected[0] += 1
                continue
            with lock:
                accepted.append((x, fut))
            time.sleep(prng.uniform(0, 0.002))

    threads = [threading.Thread(target=producer, args=(s, i))
               for i, s in enumerate(sizes)]
    for t in threads:
        t.start()
    time.sleep(0.02)
    b.stop()                                # races the producers
    for t in threads:
        t.join()
    for x, fut in accepted:
        assert fut.event.wait(10.0), "request silently dropped"
        # exactly one terminal state
        assert (fut.result is None) != (fut.error is None)
        assert fut.error is None            # no deadlines -> no sheds
        np.testing.assert_allclose(np.asarray(fut.result[0]), x * 2.0)
    st = b.stats()
    assert st["requests"] == len(accepted)
    assert st["served"] == len(accepted)
    assert st["served"] + st["shed"] == st["requests"]
    assert st["rows"] == sum(x.shape[0] for x, _ in accepted)
    assert not b._queue                     # drained, not dropped
    assert len(accepted) + rejected[0] == 6 * 25


def test_step_time_ewma_feeds_batcher():
    """The engine's measured compile-warm step times reach the batcher's
    shed/early-dispatch signal through the program cache."""
    rng = np.random.RandomState(22)
    sym = _net(with_bn=False)
    args, _ = _params_for(sym, 4, rng)
    eng = InferenceEngine(sym, args, {}, ctx=mx.cpu(), buckets=(4,),
                          async_worker=False)
    x = rng.normal(0, 1, (4, 6)).astype(np.float32)
    eng.predict_async({"data": x})
    eng.flush()                             # first run compiles: excluded
    assert eng.step_time(4) is None
    eng.predict_async({"data": x})
    eng.flush()                             # warm run: sampled
    assert eng.step_time(4) is not None and eng.step_time(4) > 0
    assert eng.stats()["step_time_ms"]["4"] > 0
    eng.stop()


# ---------------------------------------------------------------------------
# quantized-engine hot-swap (ISSUE 8 satellite bugfix): update_params /
# reload_from must re-fold raw fp32 weights through quantize_params
# ---------------------------------------------------------------------------

def _qnet():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="qfc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="qfc1")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _qnet_params(rng):
    return {
        "qfc0_weight": mx.nd.array(rng.normal(0, 0.4, (8, 6)).astype(np.float32)),
        "qfc0_bias": mx.nd.array(rng.normal(0, 0.1, (8,)).astype(np.float32)),
        "qfc1_weight": mx.nd.array(rng.normal(0, 0.3, (3, 8)).astype(np.float32)),
        "qfc1_bias": mx.nd.array(np.zeros(3, np.float32)),
    }


def test_quantized_engine_hot_swap_refolds_fp32():
    """Regression (ISSUE 8): update_params on a quantized engine used to
    stage raw fp32 arrays over the per-channel int8 weight buffers —
    wrong dtype, wrong scale after the first rollover. It must re-fold
    through quantize_params: same weights -> bitwise-stable outputs and
    zero new compiles; new weights -> bitwise-equal to a fresh engine
    built from quantize_params(new)."""
    from mxnet_tpu.contrib import quantization as Q
    rng = np.random.RandomState(23)
    sym = _qnet()
    params = _qnet_params(rng)
    weights = ["qfc0_weight", "qfc1_weight"]
    qsym = Q.quantize_graph(sym, offline_params=weights)
    qargs = Q.quantize_params(qsym, params)
    eng = InferenceEngine(qsym, qargs, {}, ctx=mx.cpu(), buckets=(4,),
                          async_worker=False)
    x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
    out1 = np.asarray(eng.predict({"data": x})[0])
    assert eng._params["qfc0_weight_quantize"].dtype == np.int8
    assert eng.compiles == 1

    # hot-swap with the SAME raw fp32 params: bitwise-stable, no compiles
    eng.update_params(params)
    assert eng._params["qfc0_weight_quantize"].dtype == np.int8
    out2 = np.asarray(eng.predict({"data": x})[0])
    np.testing.assert_array_equal(out1, out2)
    assert eng.compiles == 1

    # hot-swap with NEW fp32 params == fresh engine folded from them
    new_params = _qnet_params(np.random.RandomState(24))
    eng.update_params(new_params)
    assert eng.compiles == 1                # still zero recompiles
    out3 = np.asarray(eng.predict({"data": x})[0])
    ref_eng = InferenceEngine(qsym, Q.quantize_params(qsym, new_params),
                              {}, ctx=mx.cpu(), buckets=(4,),
                              async_worker=False)
    np.testing.assert_array_equal(
        out3, np.asarray(ref_eng.predict({"data": x})[0]))
    assert not np.array_equal(out1, out3)   # the swap actually happened

    # wrong-dtype buffer under the int8 name is rejected, not staged
    with pytest.raises(MXNetError, match="must be int8"):
        eng.update_params({"qfc0_weight_quantize":
                           np.zeros((8, 6), np.float32)})


def test_quantized_engine_accepts_raw_fp32_at_build():
    """An engine built straight from a training checkpoint (raw fp32,
    base-named) folds once at construction and matches the pre-folded
    build bitwise."""
    from mxnet_tpu.contrib import quantization as Q
    rng = np.random.RandomState(25)
    params = _qnet_params(rng)
    qsym = Q.quantize_graph(_qnet(), offline_params=["qfc0_weight",
                                                     "qfc1_weight"])
    eng_raw = InferenceEngine(qsym, params, {}, ctx=mx.cpu(), buckets=(4,),
                              async_worker=False)
    eng_folded = InferenceEngine(qsym, Q.quantize_params(qsym, params), {},
                                 ctx=mx.cpu(), buckets=(4,),
                                 async_worker=False)
    assert eng_raw._params["qfc0_weight_quantize"].dtype == np.int8
    x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
    np.testing.assert_array_equal(
        np.asarray(eng_raw.predict({"data": x})[0]),
        np.asarray(eng_folded.predict({"data": x})[0]))


def test_quantized_reload_from_hot_swap(tmp_path):
    """The checkpoint poller path: reload_from loads raw fp32 params and
    the quantized engine re-folds them — int8 staging preserved, compile
    count unchanged, outputs bitwise-equal to a fresh fold."""
    from mxnet_tpu.contrib import quantization as Q
    rng = np.random.RandomState(26)
    params = _qnet_params(rng)
    qsym = Q.quantize_graph(_qnet(), offline_params=["qfc0_weight",
                                                     "qfc1_weight"])
    eng = InferenceEngine(qsym, Q.quantize_params(qsym, params), {},
                          ctx=mx.cpu(), buckets=(4,), async_worker=False)
    x = rng.uniform(-1, 1, (4, 6)).astype(np.float32)
    np.asarray(eng.predict({"data": x})[0])
    assert eng.compiles == 1
    new_params = _qnet_params(np.random.RandomState(27))
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path))
    mgr.save(5, arg_params=new_params, blocking=True)
    assert eng.reload_from(str(tmp_path)) == 5
    assert eng._params["qfc0_weight_quantize"].dtype == np.int8
    out = np.asarray(eng.predict({"data": x})[0])
    assert eng.compiles == 1                # rollover compiled nothing
    ref = InferenceEngine(qsym, Q.quantize_params(qsym, new_params), {},
                          ctx=mx.cpu(), buckets=(4,), async_worker=False)
    np.testing.assert_array_equal(out, np.asarray(
        ref.predict({"data": x})[0]))
    eng.stop()


# ---------------------------------------------------------------------------
# shed-order fairness (ISSUE 11 satellite): victims at equal slack are
# selected lowest-priority-first
# ---------------------------------------------------------------------------

def test_shed_fairness_equal_slack_low_priority_sheds_same_formation():
    """Mixed-class overload: a high-priority request and a low-priority
    request carry the SAME (already-expired) deadline. The selection
    scan reaches the high-priority one first and sheds it; before the
    fix, the equal-slack low-priority request escaped judgment once the
    batch filled with feasible traffic and SURVIVED the formation
    (pending past its deadline, and potentially served outright if the
    decaying-max estimate relaxed first). Victims at equal slack must be
    taken lowest-priority-first — i.e. within the same formation."""

    def run_batch(padded, n_real):
        return [padded["x"]]

    b = DynamicBatcher(run_batch, buckets=(1, 2), max_batch=2,
                       autostart=False)
    # same tight budget for both classes; feasible deadline-less traffic
    # fills the batch between them in EDF order (prio 2 > prio 1 > prio 0)
    high = b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=1.0,
                    priority=2)
    mid1 = b.submit({"x": np.ones((1, 1), np.float32)}, priority=1)
    mid2 = b.submit({"x": np.ones((1, 1), np.float32)}, priority=1)
    low = b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=1.0,
                   priority=0)
    time.sleep(0.02)                     # both 1 ms budgets are now spent
    group, total = b._take_group(wait=False)   # ONE formation
    assert [r.priority for r in group] == [1, 1] and total == 2
    # the high-priority victim shed at the selection front...
    assert high.done()
    with pytest.raises(DeadlineExceeded):
        high.result_wait(0.0)
    # ...and the equal-slack low-priority request shed in the SAME
    # formation (the fairness sweep), not left pending for a later one
    assert low.done(), \
        "equal-slack low-priority request survived the shedding formation"
    with pytest.raises(DeadlineExceeded):
        low.result_wait(0.0)
    assert b.stats()["shed"] == 2
    b._run_group(group, total)
    assert mid1.done() and mid2.done()
    assert b.stats()["served"] == 2
    assert b.stats()["served"] + b.stats()["shed"] == b.stats()["requests"]


def test_shed_fairness_sweep_only_runs_when_shedding_engages():
    """Healthy traffic pays nothing: no shed at the selection front means
    no queue sweep — deadline-less and feasible requests are untouched
    beyond normal selection."""
    ests = []

    def step_time(bucket):
        ests.append(bucket)
        return 0.001

    b = DynamicBatcher(lambda p, n: [p["x"]], buckets=(1, 2), max_batch=1,
                       autostart=False, step_time=step_time)
    b.submit({"x": np.zeros((1, 1), np.float32)}, deadline_ms=10000.0)
    queued = b.submit({"x": np.zeros((1, 1), np.float32)},
                      deadline_ms=10000.0)
    group, total = b._take_group(wait=False)
    assert len(group) == 1 and not queued.done()
    assert len(b._queue) == 1            # no sweep touched the remainder
