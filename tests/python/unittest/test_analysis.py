"""tpulint static-analysis suite tests (mxnet_tpu/analysis/ — ISSUE 5).

Every shipped rule must flag a minimal seeded-violation fixture AND pass
its minimal good twin; suppression pragmas, the graph/jaxpr passes
(donation/f64/dead/bucket/infer-shape), the env registry check, the CLI
exit codes, and the MXNET_TPU_LINT runtime hooks are covered too. The
final test asserts the shipped tree itself lints green — the acceptance
contract of the CI `lint` stage.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.analysis import (check_bucket_escape, check_donation,
                                check_donation_aliasing,
                                check_infer_shape_consistency,
                                check_jaxpr_dead, check_jaxpr_f64,
                                check_symbol_f64, check_symbol_unused_args,
                                lint_source)
from mxnet_tpu.analysis.lint import find_registry, lint_paths, main
from mxnet_tpu.analysis.rules import is_hot_path

_REPO = os.path.normpath(os.path.join(os.path.dirname(__file__),
                                      "..", "..", ".."))

REGISTRY = open(os.path.join(_REPO, "docs", "faq", "env_var.md")).read()


def _lint(src, path="pkg/module/hot.py", hot=None, registry=REGISTRY):
    return lint_source(textwrap.dedent(src), path, hot=hot,
                       registry_text=registry)


def _active(findings, rule=None):
    return [f for f in findings if not f.suppressed
            and (rule is None or f.rule_id == rule)]


# ----------------------------------------------------------------------
# TPL101 host-sync
# ----------------------------------------------------------------------
class TestHostSync:
    def test_asnumpy_flagged_on_hot_path(self):
        bad = _lint("def f(arr):\n    return arr.asnumpy()\n")
        assert [f.rule_id for f in _active(bad)] == ["TPL101"]
        assert _active(bad)[0].line == 2

    def test_good_twin_cold_path_clean(self):
        ok = _lint("def f(arr):\n    return arr.asnumpy()\n",
                   path="pkg/tools/cold.py")
        assert not _active(ok)

    def test_np_asarray_flagged_jnp_clean(self):
        bad = _lint("""
            import numpy as np
            def f(a):
                return np.asarray(a)
        """)
        assert _active(bad, "TPL101")
        ok = _lint("""
            import jax.numpy as jnp
            def f(a):
                return jnp.asarray(a)
        """)
        assert not _active(ok)

    def test_item_and_device_get_flagged(self):
        bad = _lint("""
            import jax
            def f(a):
                return a.item() + jax.device_get(a)
        """)
        assert len(_active(bad, "TPL101")) == 2

    def test_float_of_computed_flagged_float_of_name_clean(self):
        bad = _lint("def f(a):\n    return float(a.sum())\n")
        assert _active(bad, "TPL101")
        ok = _lint("def f(ms):\n    return float(ms) / 1000.0\n")
        assert not _active(ok)

    def test_float_of_env_read_exempt(self):
        ok = _lint("""
            import os
            def f():
                return float(os.environ.get("HOT_MS", "2"))
        """)
        assert not _active(ok, "TPL101")

    def test_hot_path_detection(self):
        assert is_hot_path("mxnet_tpu/module/module.py")
        assert is_hot_path("mxnet_tpu/serving/engine.py")
        assert is_hot_path("mxnet_tpu/parallel/tpu_step.py")
        assert is_hot_path("mxnet_tpu/io_device.py")
        assert not is_hot_path("mxnet_tpu/io.py")
        assert not is_hot_path("tools/diagnose.py")


# ----------------------------------------------------------------------
# suppression pragmas
# ----------------------------------------------------------------------
class TestPragmas:
    def test_same_line_pragma_suppresses(self):
        src = ("def f(arr):\n"
               "    return arr.asnumpy()  "
               "# tpulint: allow-host-sync host export path\n")
        fs = _lint(src)
        assert not _active(fs)
        assert fs[0].suppressed and fs[0].suppress_reason == \
            "host export path"

    def test_preceding_comment_pragma_suppresses(self):
        src = ("def f(arr):\n"
               "    # tpulint: allow-host-sync adoption at init\n"
               "    return arr.asnumpy()\n")
        assert not _active(_lint(src))

    def test_wrong_slug_does_not_suppress(self):
        src = ("def f(arr):\n"
               "    return arr.asnumpy()  "
               "# tpulint: allow-blocking-get wrong slug\n")
        assert _active(_lint(src), "TPL101")

    def test_bare_pragma_is_tpl000_and_finding_stands(self):
        src = ("def f(arr):\n"
               "    return arr.asnumpy()  # tpulint: allow-host-sync\n")
        fs = _lint(src)
        rules = sorted(f.rule_id for f in _active(fs))
        assert rules == ["TPL000", "TPL101"]

    def test_pragma_on_code_line_does_not_leak_downward(self):
        # pragma attached to a CODE line must not suppress the next line
        src = ("def f(a, b):\n"
               "    x = a.asnumpy()  # tpulint: allow-host-sync one\n"
               "    return b.asnumpy()\n")
        active = _active(_lint(src), "TPL101")
        assert len(active) == 1 and active[0].line == 3


# ----------------------------------------------------------------------
# TPL102 thread-sentinel
# ----------------------------------------------------------------------
class TestThreadSentinel:
    BAD = """
        import threading
        class W:
            def _worker(self):
                while True:
                    self.q.append(1)
            def start(self):
                t = threading.Thread(target=self._worker, daemon=True)
                t.start()
    """
    GOOD = """
        import threading
        class W:
            def __init__(self):
                self._stop = threading.Event()
            def _worker(self):
                while not self._stop.is_set():
                    self.q.append(1)
            def start(self):
                t = threading.Thread(target=self._worker, daemon=True)
                t.start()
    """

    def test_loop_without_sentinel_flagged(self):
        assert _active(_lint(self.BAD, path="x.py"), "TPL102")

    def test_stop_event_twin_clean(self):
        assert not _active(_lint(self.GOOD, path="x.py"))

    def test_one_shot_thread_exempt(self):
        src = """
            import threading
            def save(fn):
                def _write():
                    fn()
                threading.Thread(target=_write, daemon=True).start()
        """
        assert not _active(_lint(src, path="x.py"))

    def test_module_level_closure_with_sentinel_clean(self):
        src = """
            import threading
            def start(stop_event):
                def worker():
                    while not stop_event.is_set():
                        pass
                threading.Thread(target=worker).start()
        """
        assert not _active(_lint(src, path="x.py"))

    def test_task_done_is_not_a_stop_path(self):
        # queue.task_done() in every worker loop must not satisfy the
        # stop-mechanism heuristic — it says nothing about shutdown
        src = """
            import threading
            class W:
                def _worker(self):
                    while True:
                        item = self.queue.get(timeout=1)
                        self.queue.task_done()
                def start(self):
                    threading.Thread(target=self._worker).start()
        """
        assert _active(_lint(src, path="x.py"), "TPL102")


# ----------------------------------------------------------------------
# TPL103 blocking-get
# ----------------------------------------------------------------------
class TestBlockingGet:
    def test_untimed_get_in_loop_flagged(self):
        bad = """
            def loop(self):
                while True:
                    job = self._queue.get()
        """
        assert _active(_lint(bad, path="x.py"), "TPL103")

    def test_timeout_twin_clean(self):
        ok = """
            def loop(self):
                while True:
                    try:
                        job = self._queue.get(timeout=1.0)
                    except Exception:
                        continue
        """
        assert not _active(_lint(ok, path="x.py"))

    def test_dict_get_and_non_loop_get_clean(self):
        ok = """
            def f(self, meta):
                x = meta.get("step")
                return self._queue.get()
        """
        assert not _active(_lint(ok, path="x.py"))

    def test_positional_block_true_flagged_false_clean(self):
        # Queue.get(block=True, timeout=None): a positional True is the
        # same forever-block as no args; a positional False cannot hang
        bad = """
            def loop(self):
                while True:
                    job = self._queue.get(True)
        """
        assert _active(_lint(bad, path="x.py"), "TPL103")
        ok = """
            def loop(self):
                while True:
                    try:
                        job = self._queue.get(False)
                    except Exception:
                        continue
        """
        assert not _active(_lint(ok, path="x.py"))
        two_positional = """
            def loop(self):
                while True:
                    job = self._queue.get(True, 1.0)
        """
        assert not _active(_lint(two_positional, path="x.py"))

    def test_timeout_none_still_flagged(self):
        # timeout=None is Queue.get's documented forever-block default —
        # spelling it out must not exempt
        bad = """
            def loop(self):
                while True:
                    job = self._queue.get(timeout=None)
        """
        assert _active(_lint(bad, path="x.py"), "TPL103")

    def test_block_true_still_flagged_block_false_clean(self):
        # only block=False (non-blocking, cannot hang) exempts — an
        # explicit block=True is the same infinite wait as no kwargs
        bad = """
            def loop(self):
                while True:
                    job = self._queue.get(block=True)
        """
        assert _active(_lint(bad, path="x.py"), "TPL103")
        ok = """
            def loop(self):
                while True:
                    try:
                        job = self._queue.get(block=False)
                    except Exception:
                        continue
        """
        assert not _active(_lint(ok, path="x.py"))


# ----------------------------------------------------------------------
# TPL104 lock-device-call
# ----------------------------------------------------------------------
class TestLockDeviceCall:
    def test_device_put_under_lock_flagged(self):
        bad = """
            import jax
            def f(self, x):
                with self._lock:
                    return jax.device_put(x)
        """
        assert _active(_lint(bad, path="x.py"), "TPL104")

    def test_jnp_compute_under_lock_flagged(self):
        bad = """
            import jax.numpy as jnp
            def f(self, x):
                with self._lock:
                    return jnp.sum(x)
        """
        assert _active(_lint(bad, path="x.py"), "TPL104")

    def test_compile_outside_lock_twin_clean(self):
        ok = """
            import jax
            def f(self, x):
                with self._lock:
                    entry = self._programs.get("k")
                return jax.device_put(x)
        """
        assert not _active(_lint(ok, path="x.py"))

    def test_nested_def_under_lock_clean(self):
        # a function DEFINED under a with-lock executes later, outside
        # the lock — its body is not lock-held code
        ok = """
            import jax.numpy as jnp
            def f(self):
                with self._lock:
                    def cb():
                        return jnp.zeros(4)
                    self._cbs.append(cb)
        """
        assert not _active(_lint(ok, path="x.py"))

    def test_metadata_and_re_compile_exempt(self):
        ok = """
            import re
            import jax
            def f(self, shape, dtype):
                with self._lock:
                    pat = re.compile("x")
                    sds = jax.ShapeDtypeStruct(shape, dtype)
                return pat, sds
        """
        assert not _active(_lint(ok, path="x.py"))


# ----------------------------------------------------------------------
# TPL105 env-registry
# ----------------------------------------------------------------------
class TestEnvRegistry:
    def test_undocumented_read_flagged(self):
        bad = """
            import os
            x = os.environ.get("MXNET_NOT_A_REAL_VAR", "0")
        """
        assert _active(_lint(bad, path="x.py"), "TPL105")

    def test_documented_read_clean(self):
        ok = """
            import os
            x = os.environ.get("MXNET_TPU_LINT", "0")
        """
        assert not _active(_lint(ok, path="x.py"))

    def test_env_flag_and_subscript_reads_covered(self):
        bad = """
            import os
            from mxnet_tpu.base import env_flag
            a = env_flag("MXNET_NOT_A_REAL_VAR")
            b = os.environ["MXNET_ALSO_NOT_REAL"]
        """
        assert len(_active(_lint(bad, path="x.py"), "TPL105")) == 2

    def test_prefix_of_documented_var_still_flagged(self):
        # whole-word registry match: MXNET_CHECKPOINT must not count as
        # documented just because MXNET_CHECKPOINT_DIR is
        bad = """
            import os
            x = os.environ.get("MXNET_CHECKPOINT", "0")
        """
        assert "MXNET_CHECKPOINT_DIR" in REGISTRY
        assert _active(_lint(bad, path="x.py"), "TPL105")

    def test_no_registry_skips_rule(self):
        bad = """
            import os
            x = os.environ.get("MXNET_NOT_A_REAL_VAR", "0")
        """
        assert not _active(_lint(bad, path="x.py", registry=None))

    def test_find_registry_walks_up(self):
        assert find_registry(os.path.join(_REPO, "mxnet_tpu")) == \
            os.path.join(_REPO, "docs", "faq", "env_var.md")


# ----------------------------------------------------------------------
# TPL106 swallowed exceptions (resilience-critical set)
# ----------------------------------------------------------------------
class TestSwallowedException:
    SCOPED = "pkg/checkpoint/manager.py"

    def test_except_pass_flagged(self):
        bad = """
            def f():
                try:
                    risky()
                except OSError:
                    pass
        """
        f = _active(_lint(bad, path=self.SCOPED))
        assert [x.rule_id for x in f] == ["TPL106"]
        # anchored on the inert body statement so the pragma reads inline
        assert f[0].line == 6

    def test_log_and_continue_flagged(self):
        bad = """
            import logging
            def f(items):
                for it in items:
                    try:
                        risky(it)
                    except Exception as e:
                        logging.warning("boom: %s", e)
                        continue
        """
        assert [x.rule_id for x in _active(_lint(bad, path=self.SCOPED))] \
            == ["TPL106"]

    def test_counter_or_reraise_or_value_return_clean(self):
        ok = """
            from mxnet_tpu import profiler
            def a():
                try:
                    risky()
                except OSError:
                    profiler.record_retry("site", "giveup")
            def b():
                try:
                    risky()
                except OSError:
                    raise
            def c():
                try:
                    return risky()
                except OSError:
                    return 0.0
            def d(self):
                try:
                    risky()
                except OSError as e:
                    self.err = e
        """
        assert not _active(_lint(ok, path=self.SCOPED))

    def test_bare_return_and_print_still_flagged(self):
        bad = """
            def f():
                try:
                    risky()
                except Exception:
                    print("oops")
                    return
        """
        assert [x.rule_id for x in _active(_lint(bad, path=self.SCOPED))] \
            == ["TPL106"]

    def test_out_of_scope_file_clean(self):
        bad = """
            def f():
                try:
                    risky()
                except OSError:
                    pass
        """
        # kvstore.py / ops are outside the resilience-critical set
        assert not _active(_lint(bad, path="pkg/ops/math.py"))

    def test_scope_detection(self):
        from mxnet_tpu.analysis.rules import is_swallow_scope
        assert is_swallow_scope("mxnet_tpu/serving/engine.py")
        assert is_swallow_scope("mxnet_tpu/checkpoint/layout.py")
        assert is_swallow_scope("mxnet_tpu/parallel/zero.py")
        assert is_swallow_scope("mxnet_tpu/io_device.py")
        assert not is_swallow_scope("mxnet_tpu/kvstore.py")
        assert not is_swallow_scope("mxnet_tpu/ops/math.py")

    def test_pragma_suppresses_with_reason(self):
        src = """
            def f():
                try:
                    risky()
                except OSError:
                    pass  # tpulint: allow-swallowed-exception unlink is best-effort cleanup
        """
        findings = _lint(src, path=self.SCOPED)
        assert not _active(findings)
        assert any(f.rule_id == "TPL106" and f.suppressed
                   for f in findings)


# ----------------------------------------------------------------------
# TPL107 wire-unpickle (ISSUE 13: pickle.loads on network-sourced bytes
# stays inside the wire.py codec seam)
# ----------------------------------------------------------------------
class TestWireUnpickle:
    SCOPED = "mxnet_tpu/serving/frontdoor.py"

    def test_loads_and_load_flagged_in_serving(self):
        bad = """
            import pickle
            def handle(payload, fh):
                a = pickle.loads(payload)
                b = pickle.load(fh)
                return a, b
        """
        f = _active(_lint(bad, path=self.SCOPED))
        assert [x.rule_id for x in f] == ["TPL107", "TPL107"]

    def test_alias_and_from_import_forms_flagged(self):
        bad = """
            import pickle as pk
            from pickle import loads as _loads
            def f(d):
                return pk.loads(d), _loads(d)
        """
        f = _active(_lint(bad, path=self.SCOPED))
        assert [x.rule_id for x in f] == ["TPL107", "TPL107"]

    def test_wire_seam_exempt(self):
        src = """
            import pickle
            def decode(payload):
                return pickle.loads(payload)
        """
        assert not _active(_lint(src, path="mxnet_tpu/serving/wire.py"),
                           rule="TPL107")

    def test_outside_serving_exempt(self):
        src = """
            import pickle
            def decode(payload):
                return pickle.loads(payload)
        """
        for path in ("mxnet_tpu/kvstore_async.py",
                     "mxnet_tpu/checkpoint/state.py",
                     "tools/diagnose.py"):
            assert not _active(_lint(src, path=path), rule="TPL107")

    def test_dumps_is_clean(self):
        # encoding is not execution — only load(s) is the hazard
        src = """
            import pickle
            def encode(obj):
                return pickle.dumps(obj)
        """
        assert not _active(_lint(src, path=self.SCOPED), rule="TPL107")

    def test_scope_helper(self):
        from mxnet_tpu.analysis.rules import is_unpickle_scope
        assert is_unpickle_scope("mxnet_tpu/serving/engine.py")
        assert is_unpickle_scope("mxnet_tpu/serving/pool.py")
        assert not is_unpickle_scope("mxnet_tpu/serving/wire.py")
        assert not is_unpickle_scope("mxnet_tpu/kvstore_async.py")

    def test_pragma_suppresses_with_reason(self):
        src = """
            import pickle
            def warm(path):
                with open(path, "rb") as fh:
                    return pickle.load(fh)  # tpulint: allow-wire-unpickle bytes come from the LOCAL warmup cache file, not a socket
        """
        findings = _lint(src, path=self.SCOPED)
        assert not _active(findings)
        assert any(f.rule_id == "TPL107" and f.suppressed
                   for f in findings)

    def test_shipped_serving_tree_is_tpl107_clean(self):
        """The seam holds on the real tree: no serving module outside
        wire.py unpickles (unsuppressed)."""
        import os
        import mxnet_tpu.serving as serving_pkg
        root = os.path.dirname(serving_pkg.__file__)
        for fname in sorted(os.listdir(root)):
            if not fname.endswith(".py"):
                continue
            path = os.path.join("mxnet_tpu", "serving", fname)
            with open(os.path.join(root, fname), encoding="utf-8") as fh:
                src = fh.read()
            findings = [f for f in lint_source(src, path) if
                        f.rule_id == "TPL107" and not f.suppressed]
            assert not findings, findings


# ----------------------------------------------------------------------
# TPL108 raw-compile (ISSUE 14: every program build stays inside the
# compile/builder.py ProgramBuilder seam)
# ----------------------------------------------------------------------
class TestRawCompile:
    SCOPED = "mxnet_tpu/serving/program_cache.py"

    def test_lower_and_compile_flagged(self):
        bad = """
            import jax
            def build(fn, sds):
                low = jax.jit(fn).lower(sds)
                return low.compile()
        """
        f = _active(_lint(bad, path=self.SCOPED))
        assert [x.rule_id for x in f] == ["TPL108", "TPL108"]

    def test_one_liner_lower_compile_flagged_twice(self):
        bad = """
            import jax
            def build(fn, sds):
                return jax.jit(fn).lower(sds).compile()
        """
        f = _active(_lint(bad, path=self.SCOPED))
        assert [x.rule_id for x in f] == ["TPL108", "TPL108"]

    def test_str_lower_and_re_compile_clean(self):
        # zero-arg .lower() is the str method; re/sre roots are compilers
        # of regexes, not programs
        src = """
            import re
            def f(name, pat):
                return name.lower(), re.compile(pat)
        """
        assert not _active(_lint(src, path=self.SCOPED), rule="TPL108")

    def test_builder_seam_exempt(self):
        src = """
            import jax
            def build(fn, sds):
                return jax.jit(fn).lower(sds).compile()
        """
        assert not _active(
            _lint(src, path="mxnet_tpu/compile/builder.py"),
            rule="TPL108")

    def test_outside_package_exempt(self):
        src = """
            import jax
            def build(fn, sds):
                return jax.jit(fn).lower(sds).compile()
        """
        for path in ("tools/cc_probe.py", "tests/python/unittest/t.py",
                     "bench.py"):
            assert not _active(_lint(src, path=path), rule="TPL108")

    def test_scope_helper(self):
        from mxnet_tpu.analysis.rules import is_raw_compile_scope
        assert is_raw_compile_scope("mxnet_tpu/executor.py")
        assert is_raw_compile_scope("mxnet_tpu/serving/program_cache.py")
        assert is_raw_compile_scope("mxnet_tpu/compile/__init__.py")
        assert not is_raw_compile_scope("mxnet_tpu/compile/builder.py")
        assert not is_raw_compile_scope("tools/tpulint.py")

    def test_pragma_suppresses_with_reason(self):
        src = """
            import jax
            def oracle(fn, sds):
                return jax.jit(fn).lower(sds).compile()  # tpulint: allow-raw-compile off-path numerics oracle, never cached or served
        """
        findings = _lint(src, path=self.SCOPED)
        assert not _active(findings)
        assert sum(1 for f in findings
                   if f.rule_id == "TPL108" and f.suppressed) == 2

    def test_shipped_tree_is_tpl108_clean(self):
        """The seam holds on the real tree: after the ISSUE-14 migration
        no mxnet_tpu module outside compile/builder.py builds a program
        raw (unsuppressed)."""
        import mxnet_tpu
        root = os.path.dirname(mxnet_tpu.__file__)
        bad = []
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.join(
                    "mxnet_tpu", os.path.relpath(full, root))
                with open(full, encoding="utf-8") as fh:
                    src = fh.read()
                bad += [f for f in lint_source(src, rel)
                        if f.rule_id == "TPL108" and not f.suppressed]
        assert not bad, bad


# ----------------------------------------------------------------------
# TPL201 f64 leaks (symbol + jaxpr)
# ----------------------------------------------------------------------
class TestF64:
    def test_symbol_f64_variable_flagged(self):
        w = mx.sym.Variable("w", dtype="float64")
        out = w * 2.0
        fs = check_symbol_f64(out)
        assert any(f.rule_id == "TPL201" and "'w'" in f.message
                   for f in fs)

    def test_symbol_f64_cast_flagged(self):
        # regression for the infer_type bug this pass exposed: a Cast to
        # exactly float64 never registered (np.dtype(None) == float64)
        out = mx.sym.Cast(mx.sym.Variable("data"), dtype="float64")
        fs = check_symbol_f64(out)
        assert any("output" in f.message for f in fs)

    def test_symbol_f32_twin_clean(self):
        out = mx.sym.Variable("w", dtype="float32") * 2.0
        assert not check_symbol_f64(out)

    def test_jaxpr_f64_flagged_under_x64(self):
        with jax.enable_x64(True):
            jx = jax.make_jaxpr(lambda x: x * 2.0)(np.zeros(3, np.float64))
        fs = check_jaxpr_f64(jx)
        assert fs and all(f.rule_id == "TPL201" for f in fs)

    def test_nested_pjit_leak_counted_once(self):
        # a pjit sub-jaxpr repeats the program invars — one leak must
        # produce one finding, not one per nesting level
        with jax.enable_x64(True):
            inner = jax.jit(lambda x: x * 2.0)
            jx = jax.make_jaxpr(lambda x: inner(x) + 1.0)(
                np.float64(1.0))
        fs = [f for f in check_jaxpr_f64(jx) if "program input" in f.message]
        assert len(fs) == 1

    def test_pjit_wrapper_outvar_not_double_counted(self):
        # the pjit eqn re-exports its sub-jaxpr's result — the inner scan
        # reports the producing op; the wrapper must not tally it again
        with jax.enable_x64(True):
            inner = jax.jit(lambda x: x.astype(np.float64) * 2.0)
            jx = jax.make_jaxpr(lambda x: inner(x))(np.float32(1.0))
        fs = check_jaxpr_f64(jx)
        assert fs  # the leak itself is reported...
        assert not [f for f in fs if "'pjit'" in f.message]  # ...once

    def test_dtypeless_aval_is_not_a_leak(self):
        # np.dtype(None) defaults to float64, so a dtype-less aval
        # (token-typed effects) must not read as f64 — the same numpy
        # trap the symbol.py Cast fix closed
        from types import SimpleNamespace as NS
        token = NS(aval=NS(shape=(), str_short=lambda: "token"))
        stub = NS(invars=[token], eqns=[], outvars=[])
        assert not check_jaxpr_f64(stub)

    def test_jaxpr_f32_twin_clean(self):
        jx = jax.make_jaxpr(lambda x: x * 2.0)(np.zeros(3, np.float32))
        assert not check_jaxpr_f64(jx)


# ----------------------------------------------------------------------
# TPL202 dead code (jaxpr + symbol)
# ----------------------------------------------------------------------
class TestDeadCode:
    def test_dead_eqn_and_unused_input_flagged(self):
        def f(a, b):
            _ = b * 2.0      # dead subgraph
            return a + 1.0   # b never reaches an output

        jx = jax.make_jaxpr(f)(np.zeros(3, np.float32),
                               np.zeros(3, np.float32))
        fs = check_jaxpr_dead(jx, input_names=["a", "b"])
        msgs = " | ".join(f.message for f in fs)
        assert "dead subgraph" in msgs and "b (" in msgs

    def test_live_twin_clean(self):
        jx = jax.make_jaxpr(lambda a, b: a + b)(
            np.zeros(3, np.float32), np.zeros(3, np.float32))
        assert not check_jaxpr_dead(jx)

    def test_constant_chain_exempt(self):
        # scalar-constant broadcasts (what every jax.vjp trace emits and
        # XLA trivially DCEs) are not user-written dead code
        def f(a):
            _ = jnp.zeros(3) * 2.0
            return a + 1.0
        jx = jax.make_jaxpr(f)(np.zeros(3, np.float32))
        assert not check_jaxpr_dead(jx)

    def test_vjp_built_program_clean(self):
        # the canonical fused-step shape — forward + vjp + update, outs
        # returned — must baseline at zero findings even though the vjp
        # trace emits constant broadcasts XLA DCEs, or the pass drowns
        # its own signal
        def step(w, x):
            out, vjp = jax.vjp(lambda p: jnp.sum((x @ p) ** 2), w)
            return w - 0.1 * vjp(jnp.ones(()))[0], out
        jx = jax.make_jaxpr(step)(np.zeros((4, 2), np.float32),
                                  np.zeros((3, 4), np.float32))
        assert not check_jaxpr_dead(jx)

    def test_discarded_primal_still_flagged(self):
        # dropping the vjp primal output leaves genuinely dead forward
        # compute (non-constant) — that stays a finding
        def step(w, x):
            out, vjp = jax.vjp(lambda p: jnp.sum((x @ p) ** 2), w)
            return w - 0.1 * vjp(jnp.ones(()))[0]
        jx = jax.make_jaxpr(step)(np.zeros((4, 2), np.float32),
                                  np.zeros((3, 4), np.float32))
        assert check_jaxpr_dead(jx)

    def test_subjaxpr_operand_not_flagged_as_unused(self):
        # a sub-jaxpr's invars belong to its outer equation (a custom_vjp
        # forward may ignore an operand the backward rule consumes) —
        # only program-boundary inputs are judged
        @jax.custom_vjp
        def f(x, label):
            return x * 2.0
        f.defvjp(lambda x, label: (f(x, label), (x, label)),
                 lambda res, g: (g * 2.0, res[1] * 0.0))
        jx = jax.make_jaxpr(lambda x, lab: f(x, lab))(
            np.zeros(3, np.float32), np.zeros(3, np.float32))
        # the operand IS consumed at the program boundary, so nothing at
        # all may be reported for it
        assert not check_jaxpr_dead(jx, input_names=["x", "lab"])

    def test_unused_rng_key_exempt(self):
        # every program threads a PRNG key by contract, even when the
        # graph is deterministic — an ignored key is never dead code
        key = jax.random.PRNGKey(0)
        jx = jax.make_jaxpr(lambda a, rng: a * 2)(
            np.zeros(3, np.float32), key)
        assert not check_jaxpr_dead(jx)
        assert not check_jaxpr_dead(jx, input_names=["a", "rng"])

    def test_symbol_unused_bind_args(self):
        out = mx.sym.Variable("a") * 2.0
        fs = check_symbol_unused_args(out, ["a", "phantom"])
        assert len(fs) == 1 and "phantom" in fs[0].message
        assert not check_symbol_unused_args(out, ["a"])


# ----------------------------------------------------------------------
# TPL203 donation contracts
# ----------------------------------------------------------------------
class TestDonation:
    ROLES = ("params", "opt_state", "aux", "batch", "batch", "rng", "lr")

    def test_train_contract_good_twin(self):
        assert not check_donation((0, 1), self.ROLES, mode="train")

    def test_train_donating_batch_flagged(self):
        fs = check_donation((0, 1, 3), self.ROLES, mode="train")
        assert len(fs) == 1 and "batch" in fs[0].message
        assert fs[0].severity == "error"

    def test_serving_contract(self):
        roles = ("batch", "params", "aux", "rng")
        assert not check_donation((0,), roles, mode="serving")
        fs = check_donation((0, 1), roles, mode="serving")
        assert len(fs) == 1 and "'params'" in fs[0].message

    def test_out_of_range_argnum_flagged(self):
        fs = check_donation((9,), self.ROLES, mode="train")
        assert fs and "position 9" in fs[0].message

    def test_aliasing_warns_when_no_output_matches(self):
        in_avals = [[((4, 4), np.float32)], [((8,), np.float32)]]
        out_avals = [((4, 4), np.float32)]
        fs = check_donation_aliasing(in_avals, out_avals, (0, 1))
        assert len(fs) == 1 and "arg 1" in fs[0].message
        assert fs[0].severity == "warning"
        assert not check_donation_aliasing(in_avals, out_avals, (0,))

    # -- ISSUE 7: the ZERO donation shape ------------------------------
    ZERO_ROLES = ("params", "opt_state_shard", "aux", "batch", "batch",
                  "rng", "lr")

    def test_train_partitioned_slot_donation_accepted(self):
        """A ZERO step that chooses to donate its partitioned (dp, chunk)
        slot blocks is contract-legal in train mode."""
        assert not check_donation((0, 1), self.ZERO_ROLES, mode="train")
        # the shipped tpu_step donates params only — also clean
        assert not check_donation((0,), self.ZERO_ROLES, mode="train")

    def test_train_batch_still_rejected_beside_partitioned_slots(self):
        fs = check_donation((0, 1, 3), self.ZERO_ROLES, mode="train")
        assert len(fs) == 1 and "batch" in fs[0].message
        assert fs[0].severity == "error"

    def test_serving_never_donates_partitioned_slots(self):
        roles = ("batch", "opt_state_shard")
        fs = check_donation((0, 1), roles, mode="serving")
        assert len(fs) == 1 and "opt_state_shard" in fs[0].message

    def test_aliasing_accepts_sharded_block_outputs(self):
        """Donated partitioned slots alias their (dp, chunk) block
        outputs; a donated arg whose blocks vanished from the outputs
        still warns."""
        blocks = [((8, 24), np.float32), ((8, 8), np.float32)]
        in_avals = [[((17, 9), np.float32), ((5,), np.float32)],  # params
                    list(blocks)]                                 # slots
        out_avals = [((17, 9), np.float32), ((5,), np.float32)] + blocks
        assert not check_donation_aliasing(in_avals, out_avals, (0, 1))
        # slots donated but the program only returns full-shape params
        fs = check_donation_aliasing(
            in_avals, [((17, 9), np.float32), ((5,), np.float32)], (0, 1))
        assert len(fs) == 1 and "arg 1" in fs[0].message


# ----------------------------------------------------------------------
# int8 program shapes (ISSUE 6): the quantized inference programs the
# serving engine compiles must pass every jaxpr sweep with ZERO findings
# — int32 accumulators are not f64 leaks, per-channel range args are not
# dead params — and resident quantized-weight buffers stay undonatable.
# ----------------------------------------------------------------------
class TestInt8ProgramShapes:
    def _quantized_jaxpr(self):
        from mxnet_tpu.contrib import quantization as Q
        rng = np.random.RandomState(0)
        data = mx.sym.Variable("data")
        net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                                 pad=(1, 1), name="c0")
        net = mx.sym.Flatten(net)
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc0")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        args = {"c0_weight": mx.nd.array(rng.normal(0, .3, (8, 3, 3, 3))),
                "c0_bias": mx.nd.array(rng.normal(0, .1, (8,))),
                "fc0_weight": mx.nd.array(rng.normal(0, .1, (4, 8 * 64))),
                "fc0_bias": mx.nd.array(np.zeros(4, np.float32))}
        qsym = Q.quantize_graph(net, th_dict={"data": 1.0, "c0": 8.0,
                                              "fc0": 16.0},
                                offline_params=list(args))
        qargs = Q.quantize_params(qsym, args)
        ba = dict(qargs, data=mx.nd.zeros((2, 3, 8, 8)),
                  softmax_label=mx.nd.zeros((2,)))
        exe = qsym.bind(mx.cpu(), ba, grad_req="null")
        names = list(exe.arg_dict) + list(exe.aux_dict)
        arg_sds = {n: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
                   for n, v in exe.arg_dict.items()}
        aux_sds = {n: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
                   for n, v in exe.aux_dict.items()}
        jx = jax.make_jaxpr(
            lambda a, x: exe._run_graph(a, x, jax.random.PRNGKey(0),
                                        False))(arg_sds, aux_sds)
        return jx, names

    def test_int32_accumulators_are_not_f64_leaks(self):
        # even under x64 (where a stray Python-float promotion WOULD
        # surface): the int8 program's int32 accumulators and range
        # arithmetic stay out of f64
        jx, _ = self._quantized_jaxpr()
        assert not check_jaxpr_f64(jx)
        with jax.enable_x64(True):
            jx64, _ = self._quantized_jaxpr()
        assert not check_jaxpr_f64(jx64)

    def test_quantized_range_args_not_dead(self):
        # per-channel min/max range args all feed the requantize/
        # dequantize/bias-fold arithmetic — none may read as dead params
        jx, names = self._quantized_jaxpr()
        assert not check_jaxpr_dead(jx)

    def test_quantized_weight_buffers_never_donated(self):
        # serving contract with a quantized model: the staged int8
        # weights are role 'params' — donating them is the same TPL203
        # error as fp32 weights, AND the aliasing pass flags that an int8
        # buffer can never alias the f32 outputs
        roles = ("batch", "params", "aux", "rng")
        fs = check_donation((1,), roles, mode="serving")
        assert len(fs) == 1 and "'params'" in fs[0].message
        in_avals = [[((4, 3, 8, 8), np.float32)],
                    [((8, 3, 3, 3), np.int8), ((8,), np.float32)]]
        out_avals = [((4, 10), np.float32)]
        fs = check_donation_aliasing(in_avals, out_avals, (1,))
        assert len(fs) == 1 and fs[0].severity == "warning"

    def test_serving_cache_compiles_int8_program_lint_clean(self, caplog):
        # end to end: the engine's bucket compile runs the MXNET_TPU_LINT
        # sweep over the real int8 program with zero findings
        from mxnet_tpu.contrib import quantization as Q
        from mxnet_tpu.serving.engine import InferenceEngine
        rng = np.random.RandomState(1)
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                  name="fc"), name="softmax")
        args = {"fc_weight": mx.nd.array(rng.normal(0, .1, (4, 16))),
                "fc_bias": mx.nd.array(np.zeros(4, np.float32))}
        qsym = Q.quantize_graph(net, th_dict={"data": 1.0, "fc": 8.0},
                                offline_params=list(args))
        qargs = Q.quantize_params(qsym, args)
        before = profiler.analysis_counters()
        os.environ["MXNET_TPU_LINT"] = "1"
        try:
            eng = InferenceEngine(qsym, qargs, {}, ctx=mx.cpu(),
                                  buckets=(4,), async_worker=False)
            eng.predict({"data": rng.normal(0, 1, (4, 16))
                         .astype(np.float32)})
        finally:
            del os.environ["MXNET_TPU_LINT"]
        after = profiler.analysis_counters()
        assert after["programs_checked"] > before.get("programs_checked", 0)
        assert after.get("findings", 0) == before.get("findings", 0)


# ----------------------------------------------------------------------
# TPL204 recompilation hazards
# ----------------------------------------------------------------------
class TestBucketEscape:
    def test_oversize_flagged(self):
        fs = check_bucket_escape(40, (1, 4, 8, 16, 32))
        assert len(fs) == 1 and fs[0].rule_id == "TPL204"

    def test_in_bucket_clean(self):
        assert not check_bucket_escape(16, (1, 4, 8, 16, 32))
        assert not check_bucket_escape(32, (1, 4, 8, 16, 32))
        assert not check_bucket_escape(7, (1, 4, 8, 16, 32))


# ----------------------------------------------------------------------
# TPL205 infer_shape consistency
# ----------------------------------------------------------------------
class _ShapeStub:
    """Symbol-shaped stub so inconsistencies can be seeded exactly."""

    def __init__(self, full, partial, full_raises=None,
                 partial_raises=None):
        self._full, self._partial = full, partial
        self._full_raises, self._partial_raises = full_raises, \
            partial_raises

    def infer_shape(self, **kw):
        if self._full_raises:
            raise self._full_raises
        return self._full

    def infer_shape_partial(self, **kw):
        if self._partial_raises:
            raise self._partial_raises
        return self._partial

    def list_arguments(self):
        return ["data", "w"]

    def list_outputs(self):
        return ["out"]

    def list_auxiliary_states(self):
        return []


class TestInferShapeConsistency:
    def test_real_symbol_consistent(self):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        assert not check_infer_shape_consistency(fc, {"data": (2, 8)})

    def test_disagreeing_concrete_shapes_flagged(self):
        full = ([(2, 8), (4, 8)], [(2, 4)], [])
        partial = ([(2, 8), (4, 9)], [(2, 4)], [])
        fs = check_infer_shape_consistency(_ShapeStub(full, partial), {})
        assert len(fs) == 1 and "'w'" in fs[0].message
        assert fs[0].severity == "error"

    def test_partial_losing_a_shape_warns(self):
        full = ([(2, 8), (4, 8)], [(2, 4)], [])
        partial = ([(2, 8), None], [(2, 4)], [])
        fs = check_infer_shape_consistency(_ShapeStub(full, partial), {})
        assert len(fs) == 1 and fs[0].severity == "warning"

    def test_strict_rejects_partial_resolves_flagged(self):
        from mxnet_tpu.base import MXNetError
        partial = ([(2, 8), (4, 8)], [(2, 4)], [])
        stub = _ShapeStub(None, partial,
                          full_raises=MXNetError("cannot infer"))
        fs = check_infer_shape_consistency(stub, {})
        assert len(fs) == 1 and "disagree" in fs[0].message

    def test_partial_raising_flagged(self):
        from mxnet_tpu.base import MXNetError
        stub = _ShapeStub(([(1,)], [(1,)], []), None,
                          partial_raises=MXNetError("boom"))
        fs = check_infer_shape_consistency(stub, {})
        assert len(fs) == 1 and "must degrade" in fs[0].message

    def test_both_raising_is_not_drift(self):
        # a genuine op-level shape bug raises from BOTH passes — that is
        # the user's bug, not strict-vs-partial drift; blaming the partial
        # pass would misattribute every plain shape error
        from mxnet_tpu.base import MXNetError
        stub = _ShapeStub(None, None,
                          full_raises=MXNetError("bad shapes"),
                          partial_raises=MXNetError("bad shapes"))
        assert not check_infer_shape_consistency(stub, {})

    def test_real_shape_bug_not_blamed_on_partial(self):
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        # 1-d data cannot feed FullyConnected: both passes raise
        assert not check_infer_shape_consistency(fc, {"data": (8,)})


# ----------------------------------------------------------------------
# runtime hooks (MXNET_TPU_LINT=1)
# ----------------------------------------------------------------------
class TestRuntimeHooks:
    def test_warmup_sweeps_program(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        profiler.analysis_counters(reset=True)
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        out = mx.sym.SoftmaxOutput(fc, name="softmax")
        ex = out.simple_bind(mx.cpu(), grad_req="null", data=(2, 8))
        ex.warmup()
        c = profiler.analysis_counters()
        assert c["programs_checked"] >= 1
        # a clean model must baseline at ZERO findings — softmax's
        # custom_vjp label operand and the threaded rng key are not dead
        assert c["findings"] == 0, c

    def test_warmup_sweeps_each_program_once(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        profiler.analysis_counters(reset=True)
        data = mx.sym.Variable("data")
        out = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        ex = out.simple_bind(mx.cpu(), grad_req="null", data=(2, 8))
        ex.warmup()
        ex.warmup()  # AOT-cache hit: no re-trace, no double count
        assert profiler.analysis_counters()["programs_checked"] == 1

    def test_program_cache_checks_serving_donation(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        profiler.analysis_counters(reset=True)
        from mxnet_tpu.serving.program_cache import BucketedProgramCache

        def fn(batch, params, aux, rng):
            return (batch["x"] * params["w"],)

        template = {"x": np.ones((4, 2), np.float32)}
        params = {"w": np.ones((2,), np.float32)}
        rng = jax.random.PRNGKey(0)
        cache = BucketedProgramCache(fn, buckets=(4,), donate=True)
        cache.warmup(template, params, {}, rng)
        # the shipped spec (batch-only donation) is contract-clean
        assert profiler.analysis_counters().get("rule:TPL203", 0) == 0
        # a spec donating the params dict (arg 1) must be flagged
        profiler.analysis_counters(reset=True)
        bad = BucketedProgramCache(fn, buckets=(2, 4), donate=False)
        bad._donate_argnums = (1,)
        bad.warmup(template, params, {}, rng)
        # the donate spec is cache-wide: ONE report, not one per bucket
        assert profiler.analysis_counters().get("rule:TPL203", 0) == 1

    def test_crashing_bind_pass_never_breaks_bind(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        from mxnet_tpu.analysis import graph_passes
        def boom(*a, **k):
            raise ValueError("not an MXNetError")
        monkeypatch.setattr(graph_passes, "check_infer_shape_consistency",
                            boom)
        out = mx.sym.Variable("a") * 2.0
        out.bind(mx.cpu(), {"a": mx.nd.zeros((2,))})  # must not raise

    def test_crashing_pass_never_breaks_the_build(self, monkeypatch):
        # the analyzer observes; a pass-level crash (jaxpr structure
        # drift across jax versions) must log, not abort the build
        from mxnet_tpu.analysis import runtime, graph_passes
        def boom(*a, **k):
            raise RuntimeError("structural drift")
        monkeypatch.setattr(graph_passes, "run_jaxpr_checks", boom)
        assert runtime.check_traced(
            lambda a: a + 1, (np.zeros(3, np.float32),), "t") == []

    def test_program_cache_flags_bucket_escape(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        profiler.analysis_counters(reset=True)
        from mxnet_tpu.serving.program_cache import BucketedProgramCache

        def fn(batch, params, aux, rng):
            return (batch["x"] * params["w"],)

        cache = BucketedProgramCache(fn, buckets=(1, 4), donate=False)
        batch = {"x": np.ones((9, 2), np.float32)}   # escapes top bucket
        params = {"w": np.ones((2,), np.float32)}
        cache.run(batch, params, {}, jax.random.PRNGKey(0))
        c = profiler.analysis_counters()
        assert c.get("rule:TPL204", 0) == 1
        # per distinct size, not per request: a steady oversized client
        # must not re-report on every dispatch
        cache.run(batch, params, {}, jax.random.PRNGKey(0))
        assert profiler.analysis_counters().get("rule:TPL204", 0) == 1
        cache.run({"x": np.ones((11, 2), np.float32)}, params, {},
                  jax.random.PRNGKey(0))
        assert profiler.analysis_counters().get("rule:TPL204", 0) == 2

    def test_tpu_step_build_checks_donation(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        profiler.analysis_counters(reset=True)
        from mxnet_tpu.parallel.mesh import data_parallel_mesh
        from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
        data = mx.sym.Variable("data")
        fc = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        out = mx.sym.SoftmaxOutput(fc, name="softmax")
        step = DataParallelTrainStep(out, data_parallel_mesh())
        step.init({"data": (8, 8), "softmax_label": (8,)})
        # the donation contract is checked at build; the jaxpr sweep
        # waits for the first step (real batch dtypes only known then)
        c = profiler.analysis_counters()
        assert c.get("rule:TPL203", 0) == 0  # shipped spec is clean
        assert c["programs_checked"] == 0
        step({"data": np.zeros((8, 8), np.float32),
              "softmax_label": np.zeros((8,), np.float32)})
        assert profiler.analysis_counters()["programs_checked"] == 1
        # second step: the sweep already ran, no re-trace
        step({"data": np.zeros((8, 8), np.float32),
              "softmax_label": np.zeros((8,), np.float32)})
        assert profiler.analysis_counters()["programs_checked"] == 1

    def test_bind_flags_unused_extra_param(self, monkeypatch):
        monkeypatch.setenv("MXNET_TPU_LINT", "1")
        profiler.analysis_counters(reset=True)
        out = mx.sym.Variable("a") * 2.0
        out.bind(mx.cpu(), {"a": mx.nd.zeros((2,)),
                            "phantom": mx.nd.zeros((3,))})
        c = profiler.analysis_counters()
        assert c.get("rule:TPL202", 0) >= 1  # phantom unused by any output

    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("MXNET_TPU_LINT", raising=False)
        profiler.analysis_counters(reset=True)
        data = mx.sym.Variable("data")
        out = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
        ex = out.simple_bind(mx.cpu(), grad_req="null", data=(2, 8))
        ex.warmup()
        assert profiler.analysis_counters()["programs_checked"] == 0


# ----------------------------------------------------------------------
# CLI / CI contract
# ----------------------------------------------------------------------
class TestCLI:
    def test_exit_one_on_seeded_violation(self, tmp_path, capsys):
        hot = tmp_path / "module"
        hot.mkdir()
        (hot / "bad.py").write_text(
            "def f(arr):\n    return arr.asnumpy()\n")
        assert main([str(tmp_path)]) == 1
        out = capsys.readouterr().out
        assert "TPL101" in out and "bad.py" in out

    def test_exit_zero_on_clean_tree(self, tmp_path, capsys):
        (tmp_path / "ok.py").write_text("x = 1\n")
        assert main([str(tmp_path)]) == 0

    def test_unparseable_file_is_a_finding(self, tmp_path):
        (tmp_path / "broken.py").write_text("def f(:\n")
        fs = lint_paths([str(tmp_path)])
        assert [f.rule_id for f in fs] == ["TPL001"]

    def test_json_format(self, tmp_path, capsys):
        hot = tmp_path / "serving"
        hot.mkdir()
        (hot / "bad.py").write_text(
            "def f(arr):\n    return arr.asnumpy()\n")
        assert main([str(tmp_path), "--format", "json"]) == 1
        import json as _json
        data = _json.loads(capsys.readouterr().out)
        assert data and data[0]["rule"] == "TPL101"

    def test_shipped_tree_lints_green(self):
        """Acceptance: `python -m mxnet_tpu.analysis.lint mxnet_tpu
        tools` exits 0 on the shipped tree (CI lint-stage contract)."""
        proc = subprocess.run(
            [sys.executable, "-m", "mxnet_tpu.analysis.lint",
             "mxnet_tpu", "tools"],
            cwd=_REPO, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 error(s)" in proc.stdout

    def test_runtime_guard_is_import_light(self):
        """The lint_enabled() guard in Executor/tpu_step/program_cache
        must not drag the AST rule engine or graph passes into every
        process (the analysis package resolves re-exports lazily)."""
        code = ("import sys\n"
                "import mxnet_tpu.analysis.runtime\n"
                "assert 'mxnet_tpu.analysis.rules' not in sys.modules\n"
                "assert 'mxnet_tpu.analysis.graph_passes' not in sys.modules\n"
                "assert 'mxnet_tpu.analysis.lint' not in sys.modules\n")
        proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_default_paths_work_from_any_cwd(self, tmp_path):
        """tools/tpulint.py promises to work from anywhere: with no path
        args the defaults resolve against the repo root, not the cwd."""
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "tpulint.py")],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "0 error(s)" in proc.stdout

    def test_ci_has_lint_stage(self):
        sys.path.insert(0, _REPO)
        try:
            import importlib
            run = importlib.import_module("ci.run")
            assert "lint" in {name for name, _ in run.STAGES}
        finally:
            sys.path.remove(_REPO)


# ----------------------------------------------------------------------
# TPL109 unsupervised-thread (ISSUE 15: every thread created in the
# long-lived-thread subsystems registers a watchdog Heartbeat)
# ----------------------------------------------------------------------
class TestUnsupervisedThread:
    SCOPED = "mxnet_tpu/serving/worker.py"

    def test_bare_thread_flagged(self):
        bad = """
            import threading
            def start(loop):
                t = threading.Thread(target=loop, daemon=True)
                t.start()
        """
        f = _active(_lint(bad, path=self.SCOPED))
        assert [x.rule_id for x in f] == ["TPL109"]

    def test_heartbeat_in_creating_function_clean(self):
        # the good twin: same Thread, but the creating function registers
        # a watchdog Heartbeat for it
        src = """
            import threading
            from mxnet_tpu.resilience.watchdog import watchdog
            def start(loop):
                t = threading.Thread(target=loop, daemon=True)
                hb = watchdog().register("w", thread=t)
                t.start()
        """
        assert not _active(_lint(src, path=self.SCOPED), rule="TPL109")

    def test_heartbeat_in_target_clean(self):
        # the worker target registering its own heartbeat also counts
        src = """
            import threading
            from mxnet_tpu.resilience.watchdog import watchdog

            def _loop():
                hb = watchdog().register("w")
                while True:
                    hb.beat()

            def start():
                threading.Thread(target=_loop, daemon=True).start()
        """
        assert not _active(_lint(src, path=self.SCOPED), rule="TPL109")

    def test_heartbeat_on_enclosing_class_clean(self):
        # registration elsewhere on the same class (e.g. the worker loop
        # method) keeps the creator clean
        src = """
            import threading
            from mxnet_tpu.resilience.watchdog import watchdog

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()

                def _run(self):
                    self._hb = watchdog().register("w", thread=self._t)
        """
        assert not _active(_lint(src, path=self.SCOPED), rule="TPL109")

    def test_out_of_scope_paths_exempt(self):
        bad = """
            import threading
            def start(loop):
                threading.Thread(target=loop).start()
        """
        for path in ("mxnet_tpu/module/module.py", "mxnet_tpu/io.py",
                     "tools/launch.py", "tests/python/unittest/t.py"):
            assert not _active(_lint(bad, path=path), rule="TPL109")

    def test_scope_helper(self):
        from mxnet_tpu.analysis.rules import is_threadwatch_scope
        assert is_threadwatch_scope("mxnet_tpu/serving/engine.py")
        assert is_threadwatch_scope("mxnet_tpu/checkpoint/manager.py")
        assert is_threadwatch_scope("mxnet_tpu/parallel/tpu_step.py")
        assert is_threadwatch_scope("mxnet_tpu/resilience/watchdog.py")
        assert is_threadwatch_scope("mxnet_tpu/io_device.py")
        assert not is_threadwatch_scope("mxnet_tpu/io.py")
        assert not is_threadwatch_scope("mxnet_tpu/module/module.py")

    def test_pragma_suppresses_with_reason(self):
        src = """
            import threading
            def start(loop):
                # tpulint: allow-unsupervised-thread short-lived join()ed helper, dies with its caller
                t = threading.Thread(target=loop, daemon=True)
                t.start()
        """
        findings = _lint(src, path=self.SCOPED)
        assert not _active(findings)
        assert any(f.rule_id == "TPL109" and f.suppressed for f in findings)

    def test_shipped_tree_is_tpl109_clean(self):
        """The supervision contract holds on the real tree: every thread
        in serving/checkpoint/parallel/resilience/io_device.py is either
        heartbeat-registered or carries a reasoned pragma."""
        import mxnet_tpu
        from mxnet_tpu.analysis.rules import is_threadwatch_scope
        root = os.path.dirname(mxnet_tpu.__file__)
        bad = []
        for dirpath, _dirnames, filenames in os.walk(root):
            for fname in filenames:
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.join("mxnet_tpu",
                                   os.path.relpath(full, root))
                if not is_threadwatch_scope(rel):
                    continue
                with open(full, encoding="utf-8") as fh:
                    src = fh.read()
                bad += [f for f in lint_source(src, rel)
                        if f.rule_id == "TPL109" and not f.suppressed]
        assert not bad, bad
