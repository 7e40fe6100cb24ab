"""Real multi-process dist-kvstore test (reference:
tests/nightly/dist_sync_kvstore.py:30-62 — aggregation exactness across
workers, here 2 CPU processes wired by tools/launch.py local through the JAX
coordination service).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))

# jaxlib's XLA:CPU client only implements cross-process collectives when
# built with the CPU collectives plugin (gloo/mpi); the stock wheel raises
# INVALID_ARGUMENT at the first psum across processes. That is a missing
# backend capability, not a dist-kvstore bug — skip with the exact evidence
# so the tests come back to life the moment the toolchain gains support
# (and still FAIL on any real regression in our own launch/kvstore path).
_NO_MULTIPROC_CPU = "Multiprocess computations aren't implemented on the " \
                    "CPU backend"


def _skip_if_cpu_collectives_unsupported(proc):
    if proc.returncode != 0 and _NO_MULTIPROC_CPU in (proc.stderr or ""):
        pytest.skip("this jaxlib's CPU backend has no cross-process "
                    "collectives (%r); two-process dist-kvstore tests "
                    "need a CPU-collectives-enabled jaxlib or a real "
                    "multi-host backend" % _NO_MULTIPROC_CPU)

WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, %(repo)r)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from mxnet_tpu.parallel.collectives import ensure_distributed
    ensure_distributed()
    import numpy as np
    import mxnet_tpu as mx

    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 2, nw
    shape = (3, 4)
    kv.init("w", mx.nd.zeros(shape))
    # each worker pushes rank+1; dist_sync must deliver the exact sum 3
    kv.push("w", mx.nd.ones(shape) * (rank + 1))
    out = mx.nd.empty(shape)
    kv.pull("w", out=out)
    got = out.asnumpy()
    # second round on another key, list API
    kv.init([9], [mx.nd.ones(shape)])
    kv.push([9], [mx.nd.ones(shape) * 2 * (rank + 1)])
    out2 = mx.nd.empty(shape)
    kv.pull([9], out=[out2])
    with open(%(outdir)r + "/worker%%d.json" %% rank, "w") as f:
        json.dump({"sum1": got.tolist(), "sum2": out2.asnumpy().tolist(),
                   "rank": rank}, f)
    kv.barrier()
""")


@pytest.mark.skipif(os.environ.get("SKIP_DIST_TESTS") == "1",
                    reason="dist tests disabled")
def test_two_process_dist_sync_aggregation(tmp_path):
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER % {"repo": REPO, "outdir": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--coordinator-port", "23457", "--",
         sys.executable, str(worker_py)],
        env=env, capture_output=True, text=True, timeout=300)
    _skip_if_cpu_collectives_unsupported(proc)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for rank in range(2):
        with open(tmp_path / ("worker%d.json" % rank)) as f:
            res = json.load(f)
        # sum over workers: 1 + 2 = 3 (exactness, not approximation)
        np.testing.assert_array_equal(np.asarray(res["sum1"]),
                                      np.full((3, 4), 3.0))
        # second key: push replaces the stored value with the worker sum
        # 2*1 + 2*2 = 6
        np.testing.assert_array_equal(np.asarray(res["sum2"]),
                                      np.full((3, 4), 6.0))


TRAIN_WORKER = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, %(repo)r)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx   # package init joins the process group

    rank = jax.process_index()
    # each worker gets its own half of a shared synthetic dataset
    rng = np.random.RandomState(0)
    X = rng.normal(0, 1, (320, 10)).astype(np.float32)
    W = rng.normal(0, 1, (10, 4)).astype(np.float32)
    y = (X @ W).argmax(1).astype(np.float32)
    Xw = X[rank::2]
    yw = y[rank::2]
    it = mx.io.NDArrayIter(Xw, yw, batch_size=16, label_name="softmax_label")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=12, kvstore="dist_sync",
            optimizer_params={"learning_rate": 0.3, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian", magnitude=1.0))
    it.reset()
    acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
    args, _ = mod.get_params()
    with open(%(outdir)r + "/train%%d.json" %% rank, "w") as f:
        json.dump({"acc": float(acc),
                   "w": args["fc_weight"].asnumpy().tolist()}, f)
""")


@pytest.mark.skipif(os.environ.get("SKIP_DIST_TESTS") == "1",
                    reason="dist tests disabled")
def test_two_process_module_training_converges(tmp_path):
    """SURVEY §3.2: Module.fit over dist_sync across 2 real processes —
    both workers converge and end with IDENTICAL weights (synchronous
    data parallelism)."""
    worker_py = tmp_path / "train_worker.py"
    worker_py.write_text(TRAIN_WORKER % {"repo": REPO,
                                         "outdir": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--coordinator-port", "23459", "--",
         sys.executable, str(worker_py)],
        env=env, capture_output=True, text=True, timeout=600)
    _skip_if_cpu_collectives_unsupported(proc)
    assert proc.returncode == 0, proc.stderr[-3000:]
    results = []
    for rank in range(2):
        with open(tmp_path / ("train%d.json" % rank)) as f:
            results.append(json.load(f))
    for r in results:
        assert r["acc"] > 0.9, results
    np.testing.assert_allclose(np.asarray(results[0]["w"]),
                               np.asarray(results[1]["w"]), atol=1e-5)
