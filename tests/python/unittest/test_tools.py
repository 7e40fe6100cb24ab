"""Tools + example-path tests: bandwidth harness, data providers, launcher
command construction (reference: tools/bandwidth, tools/launch.py,
example/image-classification/common/data.py).
"""
import argparse
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import mxnet_tpu as mx

_REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", "..",
                                      ".."))
sys.path.insert(0, os.path.join(_REPO, "tools", "bandwidth"))
sys.path.insert(0, os.path.join(_REPO, "example", "image-classification"))


def test_bandwidth_measure_runs_on_mesh():
    from measure import measure
    res = measure(total_mb=4.0, num_arrays=4, iters=2,
                  devices=jax.devices()[:4])
    assert res["devices"] == 4
    assert res["gb_per_sec_per_device"] > 0
    assert abs(res["payload_mb"] - 4.0) < 0.5


def test_synthetic_data_iter():
    from common.data import SyntheticDataIter
    it = SyntheticDataIter(10, (8, 3, 16, 16), max_iter=3)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (8, 3, 16, 16)
    it.reset()
    assert len(list(it)) == 3


def test_get_rec_iter_benchmark_mode():
    from common.data import get_rec_iter
    args = argparse.Namespace(
        benchmark=1, data_train=None, data_val=None, batch_size=4,
        image_shape="3,8,8", num_classes=10, num_examples=8,
        rgb_mean="0,0,0", rgb_std="1,1,1", data_nthreads=1)
    train, val = get_rec_iter(args, None)
    b = next(iter(train))
    assert b.data[0].shape == (4, 3, 8, 8)
    assert val is None


def test_launch_local_spawns_workers(tmp_path):
    """local launcher must run N processes with rank envs set."""
    script = tmp_path / "worker.py"
    # both workers share the parent's stdout pipe: emit the line as ONE
    # write() (atomic for < PIPE_BUF) so concurrent workers can't interleave
    # mid-line the way multi-arg print()'s several writes can under load
    script.write_text(
        "import os, sys\n"
        "sys.stdout.write('RANK %s %s\\n' % (os.environ['JAX_PROCESS_ID'],\n"
        "                 os.environ['JAX_NUM_PROCESSES']))\n")
    for attempt in range(2):  # retried once: interpreter start is
        try:                  # load-sensitive when the suite runs parallel
            out = subprocess.run(
                [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
                 "-n", "2", "--launcher", "local", "--",
                 sys.executable, str(script)],
                capture_output=True, text=True, timeout=240)
            break
        except subprocess.TimeoutExpired:
            if attempt == 1:
                raise
    assert out.returncode == 0, out.stderr
    lines = sorted(l for l in out.stdout.splitlines() if l.startswith("RANK"))
    assert lines == ["RANK 0 2", "RANK 1 2"]


def test_kvstore_server_shim():
    from mxnet_tpu.kvstore_server import KVStoreServer
    KVStoreServer(mx.kvstore.create("local")).run()  # logs + returns


def test_bandwidth_harness_runs(tmp_path):
    """tools/bandwidth/measure.py produces a GB/s-per-device number on the
    virtual mesh (the judged metric's plumbing; reference
    tools/bandwidth/README.md:36-72)."""
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "bandwidth",
                                      "measure.py"),
         "--total-mb", "8", "--num-arrays", "4", "--iters", "3",
         "--cpu-devices", "4"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    import re as _re
    m = _re.search(r"([0-9.]+)\s*GB/s", out.stdout)
    assert m and float(m.group(1)) > 0, out.stdout


def test_parse_log_markdown(tmp_path):
    """tools/parse_log.py renders the fit path's log lines as a markdown
    table (reference tools/parse_log.py)."""
    log = ("INFO:root:Epoch[0] Train-accuracy=0.5\n"
           "INFO:root:Epoch[0] Time cost=1.5\n"
           "INFO:root:Epoch[0] Validation-accuracy=0.4\n"
           "INFO:root:Epoch[1] Train-accuracy=0.8\n"
           "INFO:root:Epoch[1] Time cost=1.4\n"
           "INFO:root:Epoch[1] Validation-accuracy=0.7\n")
    p = str(tmp_path / "t.log")
    with open(p, "w") as f:
        f.write(log)
    out = subprocess.check_output(
        [sys.executable, os.path.join(_REPO, "tools", "parse_log.py"), p],
        text=True)
    assert "| 0 | 0.500000 | 0.400000 | 1.500000 |" in out
    assert "| 1 | 0.800000 | 0.700000 | 1.400000 |" in out


# --- one process per chip, no hidden CPU (ISSUE 22) -------------------------

def _run_script(args, env_extra=None, drop=(), timeout=600):
    env = dict(os.environ)
    for k in drop:
        env.pop(k, None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=_REPO)


def test_import_initialises_no_backend():
    """`import mxnet_tpu` must not take the chip: a helper process that
    merely imports the package (a front-door client, a launcher's server)
    would otherwise fight the process that owns it."""
    out = _run_script(["-c", "import mxnet_tpu, mxnet_tpu.serving, "
                       "jax._src.xla_bridge as xb; "
                       "assert not xb._backends, xb._backends; "
                       "assert not xb.backends_are_initialized()"])
    assert out.returncode == 0, out.stderr[-2000:]


def test_chip_smoke_refuses_the_cpu():
    """No accelerator, no result: non-zero exit and never `"ok": true`."""
    out = _run_script([os.path.join(_REPO, "chip_smoke.py")],
                      env_extra={"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not 'tpu'" in out.stderr


def test_chip_smoke_rehearsal_runs_every_phase(tmp_path):
    """--rehearse drives the same five phases at tiny sizes with the kernels
    interpreted, keeps its compile cache where JAX_COMPILATION_CACHE_DIR
    says, and still prints no result line."""
    import json
    out = _run_script([os.path.join(_REPO, "chip_smoke.py"), "--rehearse"],
                      env_extra={"JAX_PLATFORMS": "cpu",
                                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
                      drop=("XLA_FLAGS",), timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert '"ok"' not in out.stdout
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    assert lines[0]["compile_cache"] == str(tmp_path)
    assert [l["phase"] for l in lines if "phase" in l] == [
        "start", "train", "serve", "decode", "decode_moe", "kernels"]
    assert lines[-1] == {"rehearsal": "passed", "platform": "cpu",
                         "count": 1}
    for l in lines[1:-1]:
        assert l["max_diff"] <= l["tolerance"], l
    assert os.listdir(str(tmp_path)), "nothing was cached where asked"


def test_chip_smoke_failed_phase_is_a_failed_run():
    """Any exception in any phase ends the run non-zero with no result —
    nothing records an error and carries on."""
    code = ("import sys; sys.argv = ['chip_smoke.py', '--rehearse']\n"
            "import chip_smoke\n"
            "def boom(run): raise RuntimeError('forced phase failure')\n"
            "chip_smoke.PHASES[1] = (boom,) + chip_smoke.PHASES[1][1:]\n"
            "sys.exit(chip_smoke.main())\n")
    out = _run_script(["-c", code], env_extra={"JAX_PLATFORMS": "cpu"},
                      drop=("XLA_FLAGS",))
    assert out.returncode != 0
    assert "forced phase failure" in out.stderr
    assert '"ok"' not in out.stdout and "passed" not in out.stdout


def _bench_mod():
    sys.path.insert(0, _REPO)
    import bench
    return bench


def test_bench_refuses_a_platform_it_was_not_given(monkeypatch):
    """bench.py measures the chip: any other platform needs the caller's
    explicit JAX_PLATFORMS=cpu, never a switch of its own."""
    bench = _bench_mod()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench._platform_refusal("tpu") is None
    assert "not 'tpu'" in bench._platform_refusal("cpu")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench._platform_refusal("cpu") is None


def test_bench_run_without_chip_exits_nonzero():
    """`bench.py --run` with no chip and no explicit JAX_PLATFORMS=cpu:
    JAX falls back to the CPU here, and the harness refuses it."""
    out = _run_script([os.path.join(_REPO, "bench.py"), "--run"],
                      drop=("JAX_PLATFORMS",))
    assert out.returncode != 0
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def test_scripts_default_compile_cache_is_in_the_checkout(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is unset the scripts use the FIXED
    <checkout>/.jax_cache (the path is part of the cache key: a directory
    that moves never hits); where it is set they leave it alone."""
    bench = _bench_mod()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert bench._child_env(False)["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(_REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert bench._child_env(False)["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    # host-side phase children are pinned to the CPU by the env the
    # parent builds, whatever the caller exported
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert bench._child_env(True)["JAX_PLATFORMS"] == "cpu"
    assert bench._child_env(False)["JAX_PLATFORMS"] == "tpu"



def test_kill_job_lists_launch_processes():
    """tools/kill_job.py finds processes carrying the launch.py env
    markers (dry-run; nothing is killed)."""
    import time
    env = dict(os.environ, DMLC_ROLE="worker", JAX_PLATFORMS="cpu")
    probe = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(30)"], env=env)
    try:
        # wait past fork->execve: /proc/<pid>/environ only shows the env
        # once the child has exec'd (fixed sleeps flake under load)
        deadline = time.time() + 20
        while time.time() < deadline:
            try:
                with open("/proc/%d/environ" % probe.pid, "rb") as f:
                    if b"DMLC_ROLE" in f.read():
                        break
            except OSError:
                pass
            time.sleep(0.1)
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "kill_job.py")],
            capture_output=True, text=True, timeout=60).stdout
        assert "would kill %d" % probe.pid in out, out
        # --pattern path
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "kill_job.py"),
             "--pattern", "time.sleep(30)"],
            capture_output=True, text=True, timeout=60).stdout
        assert str(probe.pid) in out, out
        assert probe.poll() is None  # dry-run must not kill
    finally:
        probe.terminate()
        probe.wait()


def test_kill_job_requires_launcher_marker():
    """A process carrying only generic JAX coordination env (an unrelated
    jax.distributed job) is never matched by the env scan, and even a
    --pattern --force hit refuses to kill it without the DMLC_ROLE
    launcher marker."""
    import time
    env = dict(os.environ, JAX_COORDINATOR_ADDRESS="127.0.0.1:1234",
               JAX_PLATFORMS="cpu")
    env.pop("DMLC_ROLE", None)
    marker = "kill_job_probe_%d" % os.getpid()
    probe = subprocess.Popen(
        [sys.executable, "-c",
         "import time; %s = 1; time.sleep(30)" % marker], env=env)
    try:
        deadline = time.time() + 20
        while time.time() < deadline:
            try:
                with open("/proc/%d/environ" % probe.pid, "rb") as f:
                    if b"JAX_COORDINATOR_ADDRESS" in f.read():
                        break
            except OSError:
                pass
            time.sleep(0.1)
        # env scan: not a launch.py job -> invisible (match the exact
        # pid token — a raw substring check flakes when the probe pid
        # prefixes another listed pid)
        import re as _re
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "kill_job.py")],
            capture_output=True, text=True, timeout=60).stdout
        assert not _re.search(r"\bkill %d\b" % probe.pid, out), out
        # pattern + --force: matched by cmdline but REFUSED for kill
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "kill_job.py"),
             "--pattern", marker, "--force"],
            capture_output=True, text=True, timeout=60).stdout
        assert "skip %d" % probe.pid in out, out
        time.sleep(0.3)
        assert probe.poll() is None  # still alive
    finally:
        probe.terminate()
        probe.wait()
