"""`dist_async` parameter server (kvstore_async.py; reference:
src/kvstore/kvstore_dist_server.h:282-294 async branch — per-push
optimizer updates, no worker barrier)."""
import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.kvstore_async import AsyncParamServer, KVStoreDistAsync

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _free_consecutive_ports(n):
    """Base port with ports base..base+n-1 all currently bindable (the
    multi-server launcher assigns server i to server_port + i)."""
    for _ in range(50):
        base = _free_port()
        try:
            socks = []
            for i in range(n):
                s = socket.socket()
                s.bind(("", base + i))
                socks.append(s)
            for s in socks:
                s.close()
            return base
        except OSError:
            for s in socks:
                s.close()
    raise RuntimeError("no %d consecutive free ports found" % n)


@pytest.fixture()
def server_env(monkeypatch):
    port = _free_port()
    server = AsyncParamServer(port, num_workers=1)
    t = threading.Thread(target=server.serve, daemon=True)
    t.start()
    assert server._ready.wait(timeout=30)
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    yield server
    server._done.set()
    t.join(timeout=10)


def test_push_updates_immediately_without_other_workers(server_env):
    """THE async semantic: a single worker's push is applied by the
    server at once — no waiting for the other workers of the group
    (reference ApplyUpdates async branch)."""
    server_env.num_workers = 4  # pretend 3 more workers exist...
    kv = mx.kv.create("dist_async")
    assert kv.type == "dist_async"
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
    w0 = np.ones((2, 3), np.float32)
    kv.init("w", mx.nd.array(w0))
    kv.push("w", mx.nd.ones((2, 3)))  # ...but push alone still updates
    out = mx.nd.empty((2, 3))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), w0 - 0.5 * 1.0, rtol=1e-6)
    assert kv.server_stats()["push_count"] == 1  # per push, not per round


def test_every_push_counts_and_compounds(server_env):
    kv = mx.kv.create("dist_async")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
    kv.init("w", mx.nd.zeros((4,)))
    for _ in range(5):
        kv.push("w", mx.nd.ones((4,)))
    out = mx.nd.empty((4,))
    kv.pull("w", out=out)
    np.testing.assert_allclose(out.asnumpy(), -0.5 * np.ones(4), rtol=1e-5)
    assert kv.server_stats()["push_count"] == 5


def test_init_first_writer_wins(server_env):
    kv = mx.kv.create("dist_async")
    kv.init("w", mx.nd.ones((3,)))
    kv.init("w", mx.nd.zeros((3,)))  # later init is a no-op (reference)
    out = mx.nd.empty((3,))
    kv.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(), np.ones(3))


def test_push_before_init_and_no_optimizer_error(server_env):
    kv = mx.kv.create("dist_async")
    with pytest.raises(mx.base.MXNetError, match="init"):
        kv.push("nope", mx.nd.ones((2,)))
    kv.init("w", mx.nd.ones((2,)))
    with pytest.raises(mx.base.MXNetError, match="optimizer"):
        kv.push("w", mx.nd.ones((2,)))


WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, %(repo)r)
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import mxnet_tpu as mx

    rank = int(os.environ["DMLC_WORKER_ID"])
    rng = np.random.RandomState(rank)
    X = rng.normal(0, 1, (96, 6)).astype(np.float32)
    y = (X.sum(axis=1) > 0).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=2), name="softmax")
    mod = mx.mod.Module(net, context=mx.tpu(0))
    metric = mx.metric.Accuracy()
    mod.fit(it, num_epoch=12, kvstore="dist_async", eval_metric=metric,
            optimizer_params={"learning_rate": 0.2})
    kv = mod._kvstore
    assert kv.type == "dist_async", kv.type
    # read the server after BOTH workers are done: under load one worker
    # can finish all its epochs before the other has pushed at all
    kv.barrier()
    stats = kv.server_stats()
    with open(%(outdir)r + "/worker%%d.json" %% rank, "w") as f:
        json.dump({"acc": metric.get()[1], "rank": rank,
                   "push_count": stats["push_count"],
                   "per_server": stats.get("per_server", [])}, f)
""")


@pytest.mark.skipif(os.environ.get("SKIP_DIST_TESTS") == "1",
                    reason="dist tests disabled")
def test_two_worker_async_training_via_launcher(tmp_path):
    """launch.py --num-servers 1 spawns the PS + 2 independent workers;
    both converge on the shared asynchronously-updated weights, and the
    server applied every push individually."""
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER % {"repo": REPO, "outdir": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    port = _free_port()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--num-servers", "1", "--server-port", str(port),
         "--launcher", "local", "--",
         sys.executable, str(worker_py)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stderr[-3000:] or proc.stdout[-2000:])
    results = [json.load(open(str(tmp_path / ("worker%d.json" % r))))
               for r in (0, 1)]
    for r in results:
        assert r["acc"] > 0.8, results
    # the server saw every individual push: 12 epochs x 6 batches x
    # 2 workers x n_params pushes, far more than one worker alone makes
    one_worker_pushes = 12 * 6 * 2  # epochs x batches x params
    assert results[0]["push_count"] > one_worker_pushes, results


def test_server_role_reference_flow(monkeypatch):
    """The reference server pattern works: create('dist_async') on a
    DMLC_ROLE=server process returns a non-dialing handle whose
    KVStoreServer(kv).run() serves (pinned by driving one RPC)."""
    from mxnet_tpu.kvstore_server import KVStoreServer
    port = _free_port()
    monkeypatch.setenv("DMLC_ROLE", "server")
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    kv = mx.kv.create("dist_async")  # must not dial the unstarted port
    with pytest.raises(mx.base.MXNetError, match="server-role"):
        kv.push("w", mx.nd.ones((2,)))
    controller = KVStoreServer(kv)
    t = threading.Thread(target=controller.run, daemon=True)
    t.start()
    monkeypatch.setenv("DMLC_ROLE", "worker")
    worker = mx.kv.create("dist_async")  # connects once serving
    worker.init("w", mx.nd.ones((2,)))
    out = mx.nd.empty((2,))
    worker.pull("w", out=out)
    np.testing.assert_array_equal(out.asnumpy(), np.ones(2))
    worker.stop_server()
    t.join(timeout=15)
    assert not t.is_alive()


def test_async_push_composes_with_compression(server_env):
    """2-bit compression applies on the worker before the async push
    (the reference's compressed dist push path — gradient values reach
    the server quantized to +-threshold steps)."""
    kv = mx.kv.create("dist_async")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv.init("w", mx.nd.zeros((64,)))
    rng = np.random.RandomState(3)
    kv.push("w", mx.nd.array(rng.normal(0, 1, (64,)).astype(np.float32)))
    out = mx.nd.empty((64,))
    kv.pull("w", out=out)
    # w = 0 - 1.0 * quantized_grad: every weight is a multiple of 0.5
    steps = out.asnumpy() / 0.5
    assert np.allclose(steps, np.round(steps), atol=1e-5)
    assert np.abs(out.asnumpy()).max() <= 0.5 + 1e-6


# ------------------------------------------------- multi-server (PSKV) --

@pytest.fixture()
def two_server_env(monkeypatch):
    """Two in-process servers on consecutive ports + the DMLC topology
    env (reference kvstore_dist.h:151 PSKV sharding scope)."""
    base = _free_consecutive_ports(2)
    servers = [AsyncParamServer(base + i, num_workers=1) for i in range(2)]
    threads = [threading.Thread(target=sv.serve, daemon=True)
               for sv in servers]
    for t in threads:
        t.start()
    for sv in servers:
        assert sv._ready.wait(timeout=30)
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(base))
    monkeypatch.setenv("DMLC_NUM_SERVER", "2")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    monkeypatch.setenv("MXNET_KVSTORE_BIGARRAY_BOUND", "4000")
    yield servers
    for sv in servers:
        sv._done.set()
    for t in threads:
        t.join(timeout=10)


def test_big_array_splits_across_servers(two_server_env):
    """Arrays over MXNET_KVSTORE_BIGARRAY_BOUND split into leading-axis
    slices, one per server — asserted via server-side key accounting
    (reference `kvstore_dist.h:151` PSKV big-array semantics)."""
    s0, s1 = two_server_env
    kv = mx.kv.create("dist_async")
    assert kv.num_servers == 2
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5))
    big = np.arange(2000 * 3, dtype=np.float32).reshape(2000, 3)  # 6000 elems
    small = np.ones((4, 4), np.float32)                           # 64 B
    kv.init("big", mx.nd.array(big))
    kv.init("small", mx.nd.array(small))
    # server-side accounting: the big key exists as one shard per server,
    # the small key landed whole on exactly one server
    assert sorted(s0._weights.keys() | s1._weights.keys()) == [
        "big#shard0", "big#shard1", "small"]
    assert s0._weights["big#shard0"].shape == (1000, 3)
    assert s1._weights["big#shard1"].shape == (1000, 3)
    assert ("small" in s0._weights) != ("small" in s1._weights)
    # push/pull round-trip reassembles the exact array
    kv.push("big", mx.nd.ones((2000, 3)))
    out = mx.nd.empty((2000, 3))
    kv.pull("big", out=out)
    np.testing.assert_allclose(out.asnumpy(), big - 0.5, rtol=1e-6)
    stats = kv.server_stats()
    assert stats["num_keys"] == 3
    assert [p["push_count"] for p in stats["per_server"]] == [1, 1]


def test_row_sparse_routes_rows_to_owning_server(two_server_env):
    """row_sparse push/pull touch only the servers owning the rows."""
    from mxnet_tpu.ndarray import sparse as mxsp
    s0, s1 = two_server_env
    kv = mx.kv.create("dist_async")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    # 1600*3 = 4800 elements >= bound (the bound counts ELEMENTS,
    # reference size() semantics) -> split 800/800
    w = np.zeros((1600, 3), np.float32)
    kv.init("emb", mx.nd.array(w))
    assert s0._weights["emb#shard0"].shape == (800, 3)
    # rows 5, 799 belong to server 0; rows 800, 1599 to server 1
    rows = np.array([5, 799, 800, 1599], np.int64)
    vals = np.ones((4, 3), np.float32)
    grad = mxsp.row_sparse_array((vals, rows), shape=(1600, 3))
    kv.push("emb", grad)
    # each server applied exactly one sparse push to its own shard
    assert s0._push_count == 1 and s1._push_count == 1
    np.testing.assert_allclose(s0._weights["emb#shard0"][5], -1.0)
    np.testing.assert_allclose(s1._weights["emb#shard1"][799], -1.0)  # 1599
    assert np.all(s0._weights["emb#shard0"][6] == 0)  # untouched rows
    # row_sparse_pull routes each requested row to its owner
    out = mxsp.zeros("row_sparse", (1600, 3))
    kv.row_sparse_pull("emb", out=out, row_ids=mx.nd.array([799, 800]))
    np.testing.assert_allclose(out.data.asnumpy(), -np.ones((2, 3)),
                               rtol=1e-6)
    np.testing.assert_array_equal(out.indices.asnumpy(), [799, 800])
    # dense destination scatter path
    dense = mx.nd.zeros((1600, 3))
    kv.row_sparse_pull("emb", out=dense, row_ids=mx.nd.array([5, 1599]))
    got = dense.asnumpy()
    np.testing.assert_allclose(got[5], -1.0)
    np.testing.assert_allclose(got[1599], -1.0)
    assert np.all(got[6] == 0)


def test_small_keys_hash_consistently(two_server_env):
    """Whole-array placement is deterministic (FNV hash, not PYTHONHASHSEED-
    randomized str hash): a fresh client maps keys to the same servers."""
    kv1 = mx.kv.create("dist_async")
    kv1.init(["a", "b", "c"], [mx.nd.ones((2,))] * 3)
    plans1 = {k: v for k, v in kv1._placements.items()}
    kv2 = mx.kv.create("dist_async")
    for k in ("a", "b", "c"):
        assert kv2._placement(k, np.ones((2,), np.float32)) == plans1[k]


@pytest.mark.skipif(os.environ.get("SKIP_DIST_TESTS") == "1",
                    reason="dist tests disabled")
def test_two_worker_two_server_sharded_training(tmp_path):
    """launch.py --num-servers 2: both workers train against a key-sharded
    PS pair, the big FC weight demonstrably splits (per-server key
    accounting from server_stats), and training still converges."""
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER % {"repo": REPO, "outdir": str(tmp_path)})
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # force the (2, 6) FC weight (12 ELEMENTS — the bound counts
    # elements, not bytes) over the big-array bound so it shards
    env["MXNET_KVSTORE_BIGARRAY_BOUND"] = "8"
    port = _free_consecutive_ports(2)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--num-servers", "2", "--server-port", str(port),
         "--launcher", "local", "--",
         sys.executable, str(worker_py)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stderr[-3000:] or proc.stdout[-2000:])
    results = [json.load(open(str(tmp_path / ("worker%d.json" % r))))
               for r in (0, 1)]
    for r in results:
        assert r["acc"] > 0.8, results
    # the sharded topology really engaged: every server holds keys, and
    # both served pushes (the workers' stats aggregate across servers)
    per = results[0]["per_server"]
    assert len(per) == 2, results
    assert all(p["num_keys"] > 0 for p in per), results
    assert all(p["push_count"] > 0 for p in per), results
