"""ProgramBuilder — the ONE lower/compile/cache seam (ISSUE 14).

Covers: key discipline (distinct donation/sharding/dtype configs never
share an executable), lowering reuse (the Executor memory-analysis path
stopped re-tracing), AOT-vs-dispatch bit parity for all four migrated
build sites (executor forward, serving buckets, fused step, ZeRO/sharded
step), the zero-overhead env-read-at-construction contract, the compile
counter family, and cross-process executable reuse through the
persistent compile cache (`MXNET_TPU_COMPILE_CACHE`).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.compile.builder import ProgramBuilder

_REPO = os.path.normpath(os.path.join(os.path.dirname(__file__),
                                      "..", "..", ".."))


def _fn(x, w):
    return ((x @ w).sum(axis=1),)


def _sds(shape=(4, 4), dtype=jnp.float32, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ----------------------------------------------------------------------
# key discipline / cache mechanics
# ----------------------------------------------------------------------
class TestKeysAndCache:
    def test_aot_compiles_once_per_program(self):
        b = ProgramBuilder(_fn, site="t.cache")
        ex, built = b.aot_info(_sds(), _sds())
        ex2, built2 = b.aot_info(_sds(), _sds())
        assert built and not built2 and ex is ex2
        assert b.compiles == 1 and b.program_count() == 1

    def test_distinct_dtypes_never_share(self):
        b = ProgramBuilder(lambda x: (x + x,), site="t.dtype")
        e32 = b.aot(_sds((8,), jnp.float32))
        ebf = b.aot(_sds((8,), jnp.bfloat16))
        assert e32 is not ebf and b.program_count() == 2
        assert b.key(_sds((8,), jnp.float32)) != b.key(_sds((8,),
                                                           jnp.bfloat16))

    def test_distinct_shardings_never_share(self):
        from jax.sharding import SingleDeviceSharding
        b = ProgramBuilder(_fn, site="t.shard")
        pin = SingleDeviceSharding(jax.devices()[0])
        plain = b.aot(_sds(), _sds())
        pinned = b.aot(_sds(sharding=pin), _sds(sharding=pin))
        assert plain is not pinned and b.program_count() == 2
        # ambiguous shape signature: dispatch refuses to guess
        assert b.lookup(jnp.ones((4, 4)), jnp.ones((4, 4))) is None

    def test_distinct_donation_configs_never_share(self):
        b_don = ProgramBuilder(_fn, site="t.don", donate_argnums=(0,))
        b_not = ProgramBuilder(_fn, site="t.nodon")
        assert b_don.aot(_sds(), _sds()) is not b_not.aot(_sds(), _sds())
        assert b_don.stats()["donate_argnums"] == (0,)
        assert b_not.stats()["donate_argnums"] == ()

    def test_dispatch_uses_aot_executable_and_matches_jit(self):
        b = ProgramBuilder(_fn, site="t.disp")
        ex = b.aot(_sds(), _sds())
        x = jnp.arange(16.0).reshape(4, 4)
        w = jnp.ones((4, 4))
        assert b.lookup(x, w) is ex
        np.testing.assert_array_equal(np.asarray(b(x, w)[0]),
                                      np.asarray(jax.jit(_fn)(x, w)[0]))
        assert b.compiles == 1  # the dispatch neither traced nor compiled

    def test_ondemand_dispatch_lands_in_same_cache(self):
        b = ProgramBuilder(_fn, site="t.ondemand")
        x = jnp.ones((2, 3))
        w = jnp.ones((3, 3))
        b(x, w)
        assert b.compiles == 1 and b.program_count() == 1
        b(x, w)  # second call: lookup hit, no new program
        assert b.compiles == 1
        # warmup of the same shapes is a cache hit too
        _, built = b.aot_info(_sds((2, 3)), _sds((3, 3)))
        assert not built

    def test_lowering_reused_by_compile(self):
        b = ProgramBuilder(_fn, site="t.lower")
        low = b.lowered(_sds(), _sds())
        assert b.lowerings == 1
        assert b.lowered(_sds(), _sds()) is low       # cached
        b.aot(_sds(), _sds())
        assert b.lowerings == 1                       # compile reused it

    def test_failed_compile_unparks_the_key(self):
        def boom(x):
            raise ValueError("trace bomb")
        b = ProgramBuilder(boom, site="t.fail")
        with pytest.raises(ValueError):
            b.aot(_sds((2,)))
        assert b.program_count() == 0
        with pytest.raises(ValueError):  # retried, not wedged on pending
            b.aot(_sds((2,)))


# ----------------------------------------------------------------------
# compile counters
# ----------------------------------------------------------------------
class TestCompileCounters:
    def test_record_and_snapshot(self):
        profiler.compile_counters(reset=True)
        profiler.record_compile("t.site", 12.5, aot=True)
        profiler.record_compile("t.site", 2.0, aot=False,
                                persistent_hit=True)
        profiler.record_compile_hit("t.site")
        c = profiler.compile_counters()
        site = c["sites"]["t.site"]
        assert site["compiles"] == 2 and site["aot"] == 1 \
            and site["ondemand"] == 1 and site["persistent_hits"] == 1 \
            and site["cache_hits"] == 1
        assert abs(site["compile_ms"] - 14.5) < 1e-9
        assert c["total"]["compiles"] >= 2
        profiler.compile_counters(reset=True)
        assert profiler.compile_counters()["sites"].get("t.site") is None

    def test_builder_records_per_site(self):
        profiler.compile_counters(reset=True)
        b = ProgramBuilder(_fn, site="t.counted")
        b.aot(_sds(), _sds())
        b.aot_info(_sds(), _sds())  # hit
        site = profiler.compile_counters()["sites"]["t.counted"]
        assert site["compiles"] == 1 and site["aot"] == 1 \
            and site["cache_hits"] == 1 and site["compile_ms"] > 0

    def test_server_health_exposes_compiles_in_window(self):
        from mxnet_tpu.serving import ModelServer
        rng = np.random.RandomState(0)
        data = mx.sym.Variable("data")
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=3, name="hfc"),
            name="softmax")
        shapes, _, _ = sym.infer_shape(data=(4, 6))
        args = {n: mx.nd.array(rng.normal(0, 1, s).astype(np.float32))
                for n, s in zip(sym.list_arguments(), shapes)
                if n not in ("data", "softmax_label")}
        srv = ModelServer()
        try:
            srv.register("hm", sym, args, ctx=mx.cpu(), buckets=(1, 4),
                         warmup_shapes={"data": (4, 6)})
            h1 = srv.health()["models"]["hm"]
            # the warmup compile stampede lands in the first window
            assert h1["compiles_in_window"] >= 2
            assert h1["compile_ms_in_window"] > 0
            h2 = srv.health()["models"]["hm"]
            assert h2["compiles_in_window"] == 0
            st = srv.stats()["hm"]["compile"]
            assert st["compiles"] >= 2 and st["aot"] >= 2
        finally:
            srv.stop()


# ----------------------------------------------------------------------
# migrated sites: bit parity + reuse
# ----------------------------------------------------------------------
def _bound_pair(seed=5):
    rng = np.random.RandomState(seed)
    data = mx.sym.Variable("data")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=4, name="pfc"),
        name="softmax")
    exes = []
    for _ in range(2):
        ex = sym.simple_bind(mx.cpu(), grad_req="null", data=(4, 6),
                             softmax_label=(4,))
        exes.append(ex)
    for n, a in exes[0].arg_dict.items():
        if n not in ("data", "softmax_label"):
            a[:] = rng.normal(0, 1, a.shape).astype(np.float32)
        a.copyto(exes[1].arg_dict[n])
    return sym, exes[0], exes[1], rng


class TestMigratedSites:
    def test_executor_warmup_vs_cold_bit_parity(self):
        _, warm, cold, rng = _bound_pair()
        warm.warmup()
        x = mx.nd.array(rng.normal(0, 1, (4, 6)).astype(np.float32))
        out_w = warm.forward(is_train=False, data=x)[0].asnumpy()
        out_c = cold.forward(is_train=False, data=x)[0].asnumpy()
        np.testing.assert_array_equal(out_w, out_c)

    def test_program_cost_reuses_one_lowering_and_executable(self):
        rng = np.random.RandomState(3)
        data = mx.sym.Variable("data")
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=4, name="cfc"),
            name="softmax")
        ex = sym.simple_bind(mx.cpu(), grad_req="write", data=(4, 6),
                             softmax_label=(4,))
        cost = ex.program_cost()
        assert cost["flops"] > 0
        fb = ex._fb_fn(False)
        assert fb.lowerings == 1 and fb.compiles == 1
        # a second analysis re-traces NOTHING (the ISSUE-14 satellite:
        # the old path lowered a second program just for memory_analysis)
        assert ex.program_cost() == cost
        assert fb.lowerings == 1 and fb.compiles == 1
        # ...and the training dispatch runs the SAME executable the
        # analysis compiled — no duplicate program for the real step
        x = mx.nd.array(rng.normal(0, 1, (4, 6)).astype(np.float32))
        ex.forward(is_train=True, data=x)
        ex.backward()
        assert fb.compiles == 1

    def test_serving_engine_matches_plain_executor(self):
        from mxnet_tpu.serving import InferenceEngine
        sym, exe, _, rng = _bound_pair(seed=11)
        params = {n: a for n, a in exe.arg_dict.items()
                  if n not in ("data", "softmax_label")}
        eng = InferenceEngine(sym, params, {}, ctx=mx.cpu(),
                              buckets=(4,), async_worker=False)
        try:
            eng.warmup({"data": (4, 6)})
            x = rng.normal(0, 1, (4, 6)).astype(np.float32)
            got = np.asarray(eng.predict({"data": x})[0])
            want = exe.forward(is_train=False,
                               data=mx.nd.array(x))[0].asnumpy()
            np.testing.assert_array_equal(got, want)
        finally:
            eng.stop()

    def test_fused_step_warmup_bit_parity(self):
        from mxnet_tpu.parallel.mesh import data_parallel_mesh
        from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                  name="wfc"), name="softmax")
        mesh = data_parallel_mesh(jax.devices()[:2])
        shapes = {"data": (8, 9), "softmax_label": (8,)}
        rngb = np.random.RandomState(0)
        batches = [{"data": rngb.normal(0, 1, (8, 9)).astype(np.float32),
                    "softmax_label": rngb.randint(0, 5, (8,)).astype(
                        np.float32)} for _ in range(3)]

        def run(warm):
            s = DataParallelTrainStep(sym, mesh, lr=0.1, optimizer="sgd",
                                      opt_hp={"momentum": 0.9})
            s.init(shapes, seed=1)
            if warm:
                s.warmup()
                assert s._step.compiles == 1  # pre-paid
            for b in batches:
                s(b)
            if warm:
                assert s._step.compiles == 1  # steps dispatched the AOT
            return s.export_params()[0]

        pa, pb = run(True), run(False)
        for k in pa:
            np.testing.assert_array_equal(pa[k], pb[k])

    @pytest.mark.skipif(len(jax.devices()) < 8,
                        reason="needs the 8-device CPU mesh")
    def test_zero_step_warmup_bit_parity(self):
        from mxnet_tpu.parallel.mesh import data_parallel_mesh
        from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=5,
                                  name="zfc"), name="softmax")
        mesh = data_parallel_mesh(jax.devices()[:8])
        shapes = {"data": (16, 9), "softmax_label": (16,)}
        rngb = np.random.RandomState(2)
        batches = [{"data": rngb.normal(0, 1, (16, 9)).astype(np.float32),
                    "softmax_label": rngb.randint(0, 5, (16,)).astype(
                        np.float32)} for _ in range(3)]

        def run(warm):
            s = DataParallelTrainStep(sym, mesh, lr=0.1, optimizer="sgd",
                                      opt_hp={"momentum": 0.9}, zero=True)
            s.init(shapes, seed=4)
            if warm:
                s.warmup()
            for b in batches:
                s(b)
            return s.export_params()[0]

        pa, pb = run(True), run(False)
        for k in pa:
            np.testing.assert_array_equal(pa[k], pb[k])

    def test_sharded_step_warmup_bit_parity(self):
        from mxnet_tpu.parallel.mesh import get_mesh
        from mxnet_tpu.parallel.sharded_step import ShardedTrainStep
        from jax.sharding import PartitionSpec as P
        mesh = get_mesh(dp=min(2, len(jax.devices())),
                        devices=jax.devices()[:min(2, len(jax.devices()))])

        def loss_fn(params, batch):
            y = batch["x"] @ params["w"] + params["b"]
            return jnp.mean((y - batch["y"]) ** 2)

        specs = {"w": P(), "b": P()}
        batch = {"x": np.ones((8, 9), np.float32) * 0.3,
                 "y": np.zeros((8, 4), np.float32)}

        def run(warm):
            st = ShardedTrainStep(loss_fn, mesh, specs, optimizer="adam",
                                  lr=1e-2)
            st.init({"w": np.ones((9, 4), np.float32),
                     "b": np.zeros((4,), np.float32)})
            if warm:
                st.warmup(batch)
                assert st._step_fn.compiles == 1
            losses = [float(st(batch)) for _ in range(3)]
            if warm:
                assert st._step_fn.compiles == 1
            return losses

        assert run(True) == run(False)

    def test_module_fit_prepays_fused_compile(self, monkeypatch):
        monkeypatch.delenv("MXNET_TPU_TRAIN_AOT", raising=False)
        rng = np.random.RandomState(0)
        X = rng.normal(0, 1, (32, 8)).astype(np.float32)
        Y = rng.randint(0, 4, (32,)).astype(np.float32)
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                  name="ffc"), name="softmax")
        mod = mx.mod.Module(sym, context=mx.cpu())
        it = mx.io.NDArrayIter(X, Y, batch_size=16,
                               label_name="softmax_label")
        mod.fit(it, num_epoch=1, kvstore="tpu_sync",
                optimizer_params={"learning_rate": 0.1})
        st = mod._fused_step
        assert st is not None
        stats = st._step.stats()
        # ONE program: warmup pre-paid it from abstract shapes and every
        # real step dispatched that executable (an AOT/dtype mismatch
        # would show as a second compile here)
        assert stats["compiles"] == 1 and stats["programs"] == 1
        site = profiler.compile_counters()["sites"]["train.fused_step"]
        assert site["aot"] >= 1


# ----------------------------------------------------------------------
# zero-overhead contract (env read at construction, never at dispatch)
# ----------------------------------------------------------------------
class TestZeroOverhead:
    def test_no_env_reads_on_dispatch_or_cached_aot(self, monkeypatch):
        b = ProgramBuilder(_fn, site="t.zero")
        b.aot(_sds((2, 2)), _sds((2, 2)))
        import mxnet_tpu.base as base

        def boom(*a, **k):
            raise AssertionError("env read on the dispatch path")

        monkeypatch.setattr(base, "get_env", boom)
        monkeypatch.setattr(base, "env_flag", boom)
        x = jnp.ones((2, 2))
        b(x, x)                      # AOT dispatch
        b.aot_info(_sds((2, 2)), _sds((2, 2)))   # cached re-request
        b(jnp.ones((3, 2)), jnp.ones((2, 2)))    # even an on-demand build

    def test_serving_cache_dispatch_env_free(self, monkeypatch):
        from mxnet_tpu.serving.program_cache import BucketedProgramCache

        def fn(batch, params, aux, rng):
            return (batch["x"] * params["w"],)

        cache = BucketedProgramCache(fn, buckets=(2,), donate=False)
        template = {"x": np.ones((2, 3), np.float32)}
        params = {"w": np.ones((3,), np.float32)}
        rng = jax.random.PRNGKey(0)
        cache.warmup(template, params, {}, rng)
        import mxnet_tpu.base as base

        def boom(*a, **k):
            raise AssertionError("env read on the serving dispatch path")

        monkeypatch.setattr(base, "get_env", boom)
        monkeypatch.setattr(base, "env_flag", boom)
        out = cache.run({"x": np.ones((2, 3), np.float32)}, params, {},
                        rng)
        assert np.asarray(out[0]).shape == (2, 3)
        assert cache.hits == 1


# ----------------------------------------------------------------------
# cross-process executable reuse (MXNET_TPU_COMPILE_CACHE)
# ----------------------------------------------------------------------
_CHILD = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, %(repo)r)
import jax, jax.numpy as jnp
from mxnet_tpu.compile.builder import ProgramBuilder
from mxnet_tpu import profiler

def fn(x, w):
    for _ in range(30):
        x = jnp.tanh(x @ w) + x
    return (x.sum(),)

b = ProgramBuilder(fn, site="xproc")
sds = jax.ShapeDtypeStruct((64, 64), jnp.float32)
t0 = time.perf_counter()
b.aot(sds, sds)
ms = (time.perf_counter() - t0) * 1e3
site = profiler.compile_counters()["sites"]["xproc"]
print(json.dumps({"ms": ms, "persistent_hits": site["persistent_hits"],
                  "cache_dir": profiler.compile_counters()[
                      "persistent_cache_dir"]}))
"""


class TestCrossProcessReuse:
    def test_warm_restart_is_cache_backed_and_faster(self, tmp_path):
        """Subprocess A compiles cold into MXNET_TPU_COMPILE_CACHE;
        subprocess B warm-starts the same program: B must report
        persistent-cache-backed compiles (the ISSUE-14 fleet cold-start
        contract). That B is faster is a rate: a chip run's to show, not
        an assertion for a loaded CPU host."""
        env = dict(os.environ)
        env["MXNET_TPU_COMPILE_CACHE"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # same 1-device program both runs

        def run():
            out = subprocess.run(
                [sys.executable, "-c", _CHILD % {"repo": _REPO}],
                env=env, capture_output=True, text=True, timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            return json.loads(out.stdout.strip().splitlines()[-1])

        cold = run()
        warm = run()
        assert cold["cache_dir"] == str(tmp_path)
        assert cold["persistent_hits"] == 0
        assert warm["persistent_hits"] >= 1  # cache-backed, reported


# ----------------------------------------------------------------------
# persistent-cache corruption tolerance (ISSUE 15 satellite: a corrupt
# entry degrades to a cache miss — recompile, never a crashed warmup)
# ----------------------------------------------------------------------
class TestCacheCorruptionTolerance:
    def test_flipped_bytes_in_cached_entry_degrade_to_miss(self, tmp_path):
        """Warm the persistent cache, flip bytes in the middle of every
        entry (a half-written file from a killed process, bit rot on
        shared disk), restart: warmup must complete by recompiling —
        corrupt entries can neither crash the build nor 'hit'."""
        env = dict(os.environ)
        env["MXNET_TPU_COMPILE_CACHE"] = str(tmp_path)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)

        def run():
            out = subprocess.run(
                [sys.executable, "-c", _CHILD % {"repo": _REPO}],
                env=env, capture_output=True, text=True, timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            return json.loads(out.stdout.strip().splitlines()[-1])

        cold = run()
        assert cold["cache_dir"] == str(tmp_path)
        entries = [os.path.join(r, f)
                   for r, _d, files in os.walk(str(tmp_path))
                   for f in files]
        assert entries, "cold run cached nothing"
        for path in entries:
            with open(path, "r+b") as fh:
                data = bytearray(fh.read())
                for i in range(len(data) // 2, min(len(data), 
                                                   len(data) // 2 + 64)):
                    data[i] ^= 0xFF
                fh.seek(0)
                fh.write(data)
        rerun = run()                        # the regression: no crash
        assert rerun["persistent_hits"] == 0, \
            "a corrupt entry must not count as a cache hit: %s" % rerun

    def test_cache_read_fault_recompiles_and_counts(self, tmp_path,
                                                    monkeypatch):
        """The compile.cache_read fault site: an injected read failure
        with a cache configured recompiles once (cache bypassed) and
        lands in the compile.cache_corrupt counter."""
        from mxnet_tpu import base as mx_base
        from mxnet_tpu.resilience import faults
        monkeypatch.setitem(mx_base._compile_cache_state, "dir",
                            str(tmp_path))
        faults.configure(
            "compile.cache_read:count=1:raise=RuntimeError,corrupt entry")
        try:
            b = ProgramBuilder(_fn, site="corrupt_fault")
            b.aot(_sds(), _sds())
        finally:
            faults.reset()
        site = profiler.compile_counters()["sites"]["corrupt_fault"]
        assert site["cache_corrupt"] == 1
        assert site["compiles"] == 1         # the recompile succeeded

    def test_compile_error_with_cache_is_reported_as_itself(self, tmp_path,
                                                            monkeypatch):
        """A genuine compile error fails the cache-bypassed retry too: the
        FIRST error is what surfaces (an XLA/Mosaic refusal must read as
        itself, not as the retry's), and nothing counts as corruption."""
        from mxnet_tpu import base as mx_base
        monkeypatch.setitem(mx_base._compile_cache_state, "dir",
                            str(tmp_path))
        b = ProgramBuilder(_fn, site="compile_error_first")
        lowered = b.lowered(_sds(), _sds())
        errors = iter([RuntimeError("first: the compiler refused"),
                       RuntimeError("second: the retry's")])

        class _Refusing:
            def compile(self):
                raise next(errors)

        monkeypatch.setitem(b._lowered, b.key(_sds(), _sds()), _Refusing())
        with pytest.raises(RuntimeError, match="first: the compiler"):
            b.aot(_sds(), _sds())
        assert lowered is not None
        site = profiler.compile_counters()["sites"].get(
            "compile_error_first", {})
        assert site.get("cache_corrupt", 0) == 0
        import jax
        assert jax.config.jax_enable_compilation_cache  # bypass undone

    def test_cache_read_fault_without_cache_surfaces(self, monkeypatch):
        """No persistent cache configured: a compile failure is a real
        compile failure — zero behavior change, the error surfaces."""
        from mxnet_tpu import base as mx_base
        from mxnet_tpu.resilience import faults
        monkeypatch.setitem(mx_base._compile_cache_state, "dir", None)
        faults.configure(
            "compile.cache_read:count=1:raise=RuntimeError,real failure")
        try:
            b = ProgramBuilder(_fn, site="corrupt_nofault")
            with pytest.raises(RuntimeError, match="real failure"):
                b.aot(_sds(), _sds())
        finally:
            faults.reset()
        site = profiler.compile_counters()["sites"].get("corrupt_nofault",
                                                        {})
        assert site.get("cache_corrupt", 0) == 0
