"""`dist_async` — a real asynchronous parameter server.

Reference: src/kvstore/kvstore_dist_server.h:282-294 — in async mode the
server applies the optimizer to EVERY worker push immediately, with no
cross-worker barrier; workers pull whatever weights the server has at
that moment (bounded staleness). This is the one reference behavior
class XLA collectives cannot express (collectives are synchronous by
construction), so it gets an actual server:

* `AsyncParamServer` — a host-side TCP server owning fp32 weights and
  the optimizer (`update_on_kvstore=True` semantics). One request loop
  serializes updates exactly like the reference engine serializes
  per-key server ops.
* `KVStoreDistAsync` — the worker client: `push` ships gradients and
  returns (no barrier), `pull` fetches current weights.

Topology: N independent server processes with deterministic client-side
key placement (reference `kvstore_dist.h:151` PSKV semantics):

* arrays smaller than `MXNET_KVSTORE_BIGARRAY_BOUND` (default 1e6
  ELEMENTS — the reference compares `size()`, not bytes; see
  `docs/faq/env_var.md`) live whole on `hash(key) % N`;
* bigger arrays split into N near-equal leading-axis slices, one per
  server — every server then shares the update work of the hot weights,
  which is exactly what made the reference's PS scale. Slices keep ROW
  boundaries so row_sparse traffic routes to the owning server directly.

The wire format is length-prefixed pickle over TCP. Like the reference's
ps-lite transport this is for TRUSTED cluster networks only: pickle
deserialization is code execution, so never expose the port beyond the
job's hosts (reference ps-lite vans are equally unauthenticated).

Env protocol (reference kvstore.h:254 InitPSEnv):
  DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT — server 0 address
  DMLC_NUM_SERVER                      — server count (default 1);
                                         server i defaults to the root
                                         host at ROOT_PORT + i
  DMLC_PS_SERVER_URIS                  — optional "host:port,host:port"
                                         override for multi-host servers
  DMLC_SERVER_ID                       — this server's index (server role)
  DMLC_ROLE                            — worker | server | scheduler
  DMLC_NUM_WORKER / DMLC_WORKER_ID     — worker identity
  DMLC_PS_BIND_ADDR                    — server listen interface
                                         (default 127.0.0.1; set "" on the
                                         server host for all-interfaces in
                                         a real multi-host cluster)
`tools/launch.py --num-servers N` wires all of it.
"""
from __future__ import annotations

import os
import pickle
import re
import socket
import threading

import numpy as _np

from .base import MXNetError
from .kvstore import KVStore, _key_list, _val_list
from .ndarray import sparse as _mx_sparse
from .ndarray.ndarray import array
from .resilience import faults as _faults
from .resilience.retry import RetryPolicy, TransientError
from .serving import wire as _wire

__all__ = ["AsyncParamServer", "KVStoreDistAsync", "serve_forever",
           "TransportError"]


class TransportError(TransientError):
    """Connection-level dist_async failure (socket error, server closed
    the connection mid-round-trip) — typed apart from application errors
    the server reports, because only transport failures of IDEMPOTENT
    operations (the pull family) are safe to retry: a retried push whose
    original the server DID apply before dying would double-apply the
    optimizer update."""


def _stable_hash(key):
    """Deterministic across processes (PYTHONHASHSEED randomizes str
    hash) — every worker must compute the same key placement."""
    h = 2166136261
    for ch in str(key).encode():
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
    return h


# framing lives in serving/wire.py (extracted there for the serving
# front door, ISSUE 11); these wrappers keep the kvstore's historical
# contract — ANY end-of-stream, clean or mid-frame, reads as None and
# the caller breaks the socket
def _send_msg(sock, obj):
    _wire.send_msg(sock, obj)


def _recv_msg(sock):
    try:
        # no frame cap: the historical transport accepted arbitrarily
        # large parameter shards (trusted peers only), and capping here
        # would misread an oversized-but-healthy reply as a dead
        # connection and retry it forever
        return _wire.recv_msg(sock, max_bytes=None)
    except _wire.FrameError:
        return None


class AsyncParamServer:
    """Single-process parameter server applying per-push updates."""

    def __init__(self, port, num_workers):
        self.port = port
        self.num_workers = num_workers
        self._weights = {}      # key -> np.ndarray (fp32 master copy)
        self._updater = None
        self._push_count = 0
        self._barrier_waiting = 0
        self._barrier_generation = 0
        # worker ranks seen in the CURRENT generation (reset lazily when
        # a new generation's first waiter arrives): a set dedupes retries
        # and lets the timeout error name the missing workers, and unlike
        # _barrier_waiting it doesn't shrink when timed-out waiters leave
        self._barrier_ranks = set()
        self._barrier_ranks_gen = 0
        self._barrier_cv = threading.Condition()
        self._done = threading.Event()
        self._ready = threading.Event()  # set once listening
        self._lock = threading.Lock()  # serializes state mutation

    # -- request handlers --------------------------------------------------

    def _handle(self, msg):
        op = msg[0]
        if op == "init":
            _, key, value = msg
            with self._lock:
                # first writer wins (reference: server keeps the first
                # initialization, others are no-ops)
                self._weights.setdefault(key, _np.asarray(value,
                                                          _np.float32))
            return ("ok",)
        if op == "push":
            _, key, grad = msg
            with self._lock:
                if key not in self._weights:
                    raise MXNetError("push before init for key %r" % key)
                if self._updater is None:
                    raise MXNetError("dist_async server has no optimizer; "
                                     "call kv.set_optimizer first")
                w = array(self._weights[key])
                g = array(_np.asarray(grad, _np.float32))
                self._updater(_updater_key(key), g, w)
                self._weights[key] = w.asnumpy()
                self._push_count += 1
                return ("ok", self._push_count)
        if op == "pull":
            _, key = msg
            with self._lock:
                if key not in self._weights:
                    raise MXNetError("pull before init for key %r" % key)
                return ("ok", self._weights[key])
        if op == "push_rows":
            # sparse push: (local row indices, row values) against this
            # server's slice; the updater sees a RowSparseNDArray grad so
            # sparse-lazy optimizer variants touch only those rows
            _, key, rows, vals = msg
            from .ndarray import sparse as _sp
            with self._lock:
                if key not in self._weights:
                    raise MXNetError("push before init for key %r" % key)
                if self._updater is None:
                    raise MXNetError("dist_async server has no optimizer; "
                                     "call kv.set_optimizer first")
                w = array(self._weights[key])
                g = _sp.row_sparse_array(
                    (_np.asarray(vals, _np.float32),
                     _np.asarray(rows, _np.int64)),
                    shape=self._weights[key].shape)
                self._updater(_updater_key(key), g, w)
                self._weights[key] = w.asnumpy()
                self._push_count += 1
                return ("ok", self._push_count)
        if op == "pull_rows":
            _, key, rows = msg
            with self._lock:
                if key not in self._weights:
                    raise MXNetError("pull before init for key %r" % key)
                idx = _np.asarray(rows, _np.int64)
                return ("ok", self._weights[key][idx])
        if op == "set_optimizer":
            _, payload = msg
            from . import optimizer as opt_mod
            with self._lock:
                if self._updater is None:
                    optimizer = pickle.loads(payload)
                    self._updater = opt_mod.get_updater(optimizer)
            return ("ok",)
        if op == "barrier":
            rank = msg[1] if len(msg) > 1 else None
            with self._barrier_cv:
                generation = self._barrier_generation
                if self._barrier_ranks_gen != generation:
                    self._barrier_ranks_gen = generation
                    self._barrier_ranks = set()
                if rank is not None:
                    self._barrier_ranks.add(rank)
                self._barrier_waiting += 1
                if self._barrier_waiting == self.num_workers:
                    self._barrier_waiting = 0
                    self._barrier_generation += 1
                    self._barrier_cv.notify_all()
                else:
                    # shorter than the client's 300s socket timeout so a
                    # TIMED-OUT barrier surfaces as a clear server error
                    # on the worker, not a raw socket.timeout
                    released = self._barrier_cv.wait_for(
                        lambda: self._barrier_generation > generation,
                        timeout=240.0)
                    if not released:
                        # decrementing _barrier_waiting is bookkeeping so
                        # a later generation can't be released by phantom
                        # waiters; the error reports the per-generation
                        # RANK SET, which retries and concurrent timeouts
                        # cannot inflate or shrink
                        self._barrier_waiting -= 1
                        seen = sorted(self._barrier_ranks)
                        missing = sorted(set(range(self.num_workers))
                                         - self._barrier_ranks)
                        raise MXNetError(
                            "barrier timed out: workers seen %s, missing "
                            "%s of %d (a worker crashed?)"
                            % (seen, missing, self.num_workers))
            return ("ok",)
        if op == "snapshot":
            # write this server's addressable shard of the training state
            # (weights + optimizer slots) to an atomic file — the
            # server-side half of checkpoint/kvshard.py
            _, path, sid, n = msg
            with self._lock:
                self._snapshot_to(path, sid, n)
            return ("ok", path)
        if op == "restore":
            _, path = msg
            with self._lock:
                self._restore_from(path)
            return ("ok",)
        if op == "install":
            # resharded restore: entries computed by the worker for THIS
            # server under a new topology
            _, entries, opt_payload = msg
            with self._lock:
                self._install_entries(entries, opt_payload)
            return ("ok",)
        if op == "stats":
            with self._lock:
                return ("ok", {"push_count": self._push_count,
                               "num_keys": len(self._weights)})
        if op == "stop":
            self._done.set()
            return ("ok",)
        raise MXNetError("unknown server op %r" % (op,))

    # -- checkpoint (server side; see checkpoint/kvshard.py) ---------------

    def _state_blob(self, sid, n):
        """Snapshot blob of this server's weights + optimizer slots.
        Caller holds the state lock. State slots key on the STRIPPED
        updater key (one shard of a key per server, so the pairing
        subkey -> state is unique)."""
        from .checkpoint.state import tree_to_numpy
        entries = {}
        states = self._updater.states if self._updater is not None else {}
        for subkey, weight in self._weights.items():
            entries[subkey] = {
                "weight": _np.asarray(weight),
                "state": tree_to_numpy(states.get(_updater_key(subkey)))}
        optimizer = None
        if self._updater is not None:
            opt = self._updater.optimizer
            try:
                optimizer = pickle.dumps(opt)
            except Exception:  # unpicklable custom optimizer: weights-only
                optimizer = None
        return {"format": 1, "server": sid, "num_servers": n,
                "entries": entries, "optimizer": optimizer,
                "push_count": self._push_count}

    def _snapshot_to(self, path, sid, n):
        from .base import atomic_write
        atomic_write(path, pickle.dumps(self._state_blob(sid, n),
                                        protocol=pickle.HIGHEST_PROTOCOL))

    def _install_entries(self, entries, opt_payload):
        from .checkpoint.state import tree_from_numpy
        if opt_payload is not None:
            # the checkpoint's optimizer carries num_update / per-key
            # counters — adopt it (reference load_optimizer_states
            # semantics), replacing any freshly set_optimizer'd one
            from . import optimizer as opt_mod
            self._updater = opt_mod.get_updater(pickle.loads(opt_payload))
        for subkey, weight, state in entries:
            self._weights[subkey] = _np.asarray(weight, _np.float32)
            if state is not None and self._updater is not None:
                self._updater.states[_updater_key(subkey)] = \
                    tree_from_numpy(state)
                self._updater.states_synced[_updater_key(subkey)] = False

    def _restore_from(self, path):
        with open(path, "rb") as f:
            blob = pickle.load(f)
        self._weights = {}
        if self._updater is not None:
            self._updater.states = {}
            self._updater.states_synced = {}
        self._install_entries(
            [(k, rec["weight"], rec.get("state"))
             for k, rec in blob.get("entries", {}).items()],
            blob.get("optimizer"))
        self._push_count = int(blob.get("push_count", 0))

    # -- serving -----------------------------------------------------------

    def serve(self):
        """Accept loop; one thread per connection (updates still serialize
        on the state lock — reference analog: per-key engine ordering)."""
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        # The transport is unauthenticated pickle (code execution), so
        # never listen on all interfaces by default: bind the loopback
        # unless the launcher says otherwise (DMLC_PS_BIND_ADDR, or "" to
        # opt back into all-interfaces for real multi-host clusters).
        srv.bind((os.environ.get("DMLC_PS_BIND_ADDR", "127.0.0.1"),
                  self.port))
        srv.listen(self.num_workers * 2)
        srv.settimeout(1.0)
        self._ready.set()
        threads = []
        try:
            while not self._done.is_set():
                try:
                    conn, _ = srv.accept()
                except socket.timeout:
                    continue
                t = threading.Thread(target=self._serve_conn, args=(conn,),
                                     daemon=True)
                t.start()
                threads.append(t)
        finally:
            srv.close()
        for t in threads:
            t.join(timeout=5.0)

    def _serve_conn(self, conn):
        with conn:
            while not self._done.is_set():
                try:
                    msg = _recv_msg(conn)
                except OSError:
                    return
                if msg is None:
                    return
                try:
                    reply = self._handle(msg)
                except Exception as e:  # surfaces on the WORKER
                    reply = ("error", "%s: %s" % (type(e).__name__, e))
                try:
                    _send_msg(conn, reply)
                except OSError:
                    return


# THE shard-subkey wire format, shared with checkpoint/kvshard.py's
# split_subkey — one definition so checkpoint merge and optimizer-key
# stripping can never drift apart
SHARD_KEY_RE = re.compile(r"^(?P<base>.*)#shard(?P<idx>\d+)$")


def _updater_key(key):
    """Optimizer-facing key for a server subkey: the `#shardN` suffix is
    stripped (per-key `lr_mult`/`wd_mult`/`idx2name` settings must apply
    to every shard of a parameter, and sharded checkpoints must key state
    by the real parameter), then int when possible — optimizer per-index
    state dicts key on ints. Each server holds at most one shard of a
    key, so stripped keys stay unique server-side."""
    m = SHARD_KEY_RE.match(str(key))
    key = m.group("base") if m else str(key)
    try:
        return int(key)
    except (TypeError, ValueError):
        return key


def _server_endpoints():
    """(host, port) per server from the DMLC env: explicit
    DMLC_PS_SERVER_URIS list, else root host at ROOT_PORT + i."""
    uris = os.environ.get("DMLC_PS_SERVER_URIS", "")
    if uris:
        out = []
        for ep in uris.split(","):
            host, _, port = ep.strip().rpartition(":")
            out.append((host, int(port)))
        return out
    host = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
    port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
    n = int(os.environ.get("DMLC_NUM_SERVER", "1"))
    return [(host, port + i) for i in range(n)]


def serve_forever():
    """Entry for a DMLC_ROLE=server process (kvstore_server.py hook).

    The server is a host-side component: pin jax to CPU before the first
    device use (the optimizer update math) so it never asks for a chip
    that a worker on the same host holds."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    sid = int(os.environ.get("DMLC_SERVER_ID", "0"))
    endpoints = _server_endpoints()
    if not 0 <= sid < len(endpoints):
        raise MXNetError("DMLC_SERVER_ID=%d outside the %d-server topology"
                         % (sid, len(endpoints)))
    n = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    AsyncParamServer(endpoints[sid][1], n).serve()


class KVStoreDistAsync(KVStore):
    """Worker client: per-push server updates, no worker barrier.

    Key placement mirrors the reference PSKV (`kvstore_dist.h:151`):
    small arrays hash to one server; arrays of
    MXNET_KVSTORE_BIGARRAY_BOUND or more elements split into near-equal
    leading-axis slices, one per server."""

    def __init__(self):
        super().__init__("dist_async")
        self._rank = int(os.environ.get("DMLC_WORKER_ID", "0"))
        self._num_workers = int(os.environ.get("DMLC_NUM_WORKER", "1"))
        self._socks = None
        self._sock_locks = None
        self._placements = {}   # key -> list of per-server row slices
        self._bigarray_bound = int(float(os.environ.get(
            "MXNET_KVSTORE_BIGARRAY_BOUND", "1000000")))
        role = os.environ.get("DMLC_ROLE", "worker")
        if role in ("server", "scheduler"):
            # reference server flow: `kv = mx.kv.create('dist_async');
            # KVStoreServer(kv).run()` — the server process must NOT dial
            # its own (not-yet-listening) port; this instance is just the
            # handle run() reads the type from
            return
        if not os.environ.get("DMLC_PS_ROOT_URI"):
            raise MXNetError(
                "kvstore dist_async needs a parameter server: launch via "
                "`tools/launch.py -n <workers> --num-servers N` (sets "
                "DMLC_PS_ROOT_URI/PORT), or start "
                "`python -m mxnet_tpu.kvstore_server` with DMLC_ROLE=server")
        self._endpoints = _server_endpoints()
        self._socks = [self._connect_with_retry(host, port)
                       for host, port in self._endpoints]
        self._sock_locks = [threading.Lock() for _ in self._socks]
        # transport retry: IDEMPOTENT round-trips only (see _rpc_scatter);
        # each attempt reconnects whatever sockets the last one broke
        self._idempotent_retry = RetryPolicy(site="kvstore.pull",
                                             retryable=TransportError)

    @property
    def num_servers(self):
        return len(self._socks) if self._socks else 0

    @staticmethod
    def _connect_with_retry(uri, port, deadline_s=60.0):
        """The server process may still be binding when workers start
        (launch.py spawns both concurrently) — retry under the unified
        backoff policy until the deadline budget runs out."""
        policy = RetryPolicy(attempts=1000, base_delay_s=0.05,
                             cap_delay_s=0.5, deadline_s=deadline_s,
                             retryable=OSError, site="kvstore.connect")
        try:
            return policy.call(socket.create_connection, (uri, port),
                               timeout=300.0)
        except OSError as e:
            raise MXNetError(
                "could not reach dist_async server at %s:%d within "
                "%.0fs (%s). If the server runs on another host, "
                "it binds 127.0.0.1 by default — set "
                "DMLC_PS_BIND_ADDR on the server (empty string = "
                "all interfaces; trusted networks only)"
                % (uri, port, deadline_s, e)) from e

    # identity from the DMLC env, NOT jax.process_*: async workers are
    # independent processes, no jax.distributed mesh exists
    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._num_workers

    def _require_worker(self):
        if self._socks is None:
            raise MXNetError(
                "this dist_async kvstore is a server-role handle "
                "(DMLC_ROLE=%s): pass it to KVStoreServer(kv).run() — "
                "worker API calls belong on worker processes"
                % os.environ.get("DMLC_ROLE"))

    def _rpc(self, server, *msg, idempotent=False):
        return self._rpc_scatter([(server, msg)],
                                 idempotent=idempotent)[0]

    def _rpc_scatter(self, calls, idempotent=False):
        """One round-trip to several servers, overlapped: send every
        request first, then collect replies — per-key shard latency is
        max(server round-trips), not their sum. `calls` is
        [(server, msg tuple)] with at most one call per server.

        ``idempotent=True`` (the pull/stats family — reads with no
        server-side effect) retries TRANSPORT failures under the unified
        backoff policy, reconnecting broken sockets between attempts.
        Effectful ops (push, init, set_optimizer, barrier) never retry:
        a server may have applied the original before the connection
        died, and re-applying a push double-counts the gradient."""
        if idempotent:
            return self._idempotent_retry.call(self._rpc_scatter_once,
                                               calls)
        return self._rpc_scatter_once(calls)

    def _reconnect_locked(self, s):
        """Rebuild server `s`'s socket (caller holds its lock). A short
        deadline: the retry policy above owns the long-haul waiting."""
        host, port = self._endpoints[s]
        self._socks[s] = self._connect_with_retry(host, port,
                                                  deadline_s=10.0)
        return self._socks[s]

    def _break_locked(self, s):
        """Mark server `s`'s connection dead (caller holds its lock): a
        half-finished round-trip leaves an unreadable request/reply
        stream, so the socket must never be reused."""
        sock = self._socks[s]
        self._socks[s] = None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass  # tpulint: allow-swallowed-exception socket already dead; close is best-effort hygiene
        return TransportError("dist_async server %d connection broken" % s)

    def _rpc_scatter_once(self, calls):
        self._require_worker()
        for s, _ in calls:
            self._sock_locks[s].acquire()
        try:
            sent = []
            for s, msg in calls:
                sock = self._socks[s]
                if sock is None:  # broken by a previous round-trip
                    sock = self._reconnect_locked(s)
                try:
                    _send_msg(sock, msg)
                except OSError as e:
                    # a half-sent scatter poisons EVERY socket already
                    # sent to this attempt: their replies will arrive
                    # unread, and reusing such a connection would pair
                    # the NEXT request with this round's stale reply.
                    # Break them all so a retry reconnects fresh.
                    err = self._break_locked(s)
                    for prev in sent:
                        self._break_locked(prev)
                    raise err from e
                sent.append(s)
            # drain EVERY reply before raising: leaving an unread reply in
            # a socket buffer desyncs that connection's request/reply
            # protocol for good (the next RPC would read this stale one)
            replies, errors, transport_only = [], [], True
            for s, _ in calls:
                try:
                    reply = _recv_msg(self._socks[s])
                except OSError:
                    reply = None
                if reply is None:
                    self._break_locked(s)
                    errors.append("server %d closed the connection" % s)
                elif reply[0] == "error":
                    transport_only = False
                    errors.append("server %d: %s" % (s, reply[1]))
                else:
                    replies.append(reply)
            if errors:
                # typed: pure connection-level failure is retryable (for
                # idempotent calls); any APPLICATION error from a server
                # must surface as-is, never be retried into a double-apply
                cls = TransportError if transport_only else MXNetError
                raise cls("dist_async " + "; ".join(errors))
            return replies
        finally:
            for s, _ in calls:
                self._sock_locks[s].release()

    # -- key placement (reference kvstore_dist.h:151 PSKV) -----------------

    def _placement(self, key, arr):
        """[(server, row_start, row_stop)] for `key` with shape/dtype of
        `arr`; whole-array placements use (server, None, None). Computed
        once per key at init and reused by every push/pull (the
        reference caches PSKV the same way)."""
        if key in self._placements:
            return self._placements[key]
        self._require_worker()
        n = len(self._socks)
        shape = arr.shape
        # the bound counts ELEMENTS (reference kvstore_dist.h compares
        # size(), and model.py's big-array split uses prod(shape)), not
        # bytes-assuming-float32
        size = int(_np.prod(shape, dtype=_np.int64)) if shape else 1
        if n == 1 or size < self._bigarray_bound or not shape \
                or shape[0] < n:
            plan = [(_stable_hash(key) % n, None, None)]
        else:
            rows = shape[0]
            bounds = [rows * i // n for i in range(n + 1)]
            plan = [(s, bounds[s], bounds[s + 1]) for s in range(n)
                    if bounds[s] < bounds[s + 1]]
        self._placements[key] = plan
        return plan

    @staticmethod
    def _subkey(key, server, whole):
        return key if whole else "%s#shard%d" % (key, server)

    # -- KVStore API -------------------------------------------------------

    def init(self, key, value):
        keys, _ = _key_list(key)
        vals = _val_list(value, len(keys))
        for k, vlist in zip(keys, vals):
            val = vlist[0].asnumpy()
            self._rpc_scatter(
                [(s, ("init", self._subkey(str(k), s, r0 is None),
                      val if r0 is None else val[r0:r1]))
                 for s, r0, r1 in self._placement(str(k), val)])

    def push(self, key, value, priority=0):
        keys, _ = _key_list(key)
        vals = _val_list(value, len(keys))
        for k, vlist in zip(keys, vals):
            _faults.fault_point("kvstore.push", key=str(k))
            if self._gc.active:
                vlist = self._compress_vlist(str(k), vlist)
            merged = self._merge(vlist)
            if isinstance(merged, _mx_sparse.RowSparseNDArray):
                self._push_row_sparse(str(k), merged)
                continue
            grad = merged.asnumpy()
            self._rpc_scatter(
                [(s, ("push", self._subkey(str(k), s, r0 is None),
                      grad if r0 is None else grad[r0:r1]))
                 for s, r0, r1 in self._placement(str(k), grad)])

    def _push_row_sparse(self, key, merged):
        """Route row_sparse gradient rows to their owning servers."""
        rows = merged.indices.asnumpy().astype(_np.int64)
        vals = merged.data.asnumpy()
        plan = self._placement(key, merged)
        calls = []
        for s, r0, r1 in plan:
            if r0 is None:
                calls.append((s, ("push_rows", key, rows, vals)))
                continue
            mask = (rows >= r0) & (rows < r1)
            if mask.any():
                calls.append((s, ("push_rows", self._subkey(key, s, False),
                                  rows[mask] - r0, vals[mask])))
        self._rpc_scatter(calls)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, _ = _key_list(key)
        outs = _val_list(out, len(keys))
        for k, olist in zip(keys, outs):
            _faults.fault_point("kvstore.pull", key=str(k))
            # placement is derivable from the out buffer, so a fresh
            # process (worker restart, eval-only attach) can pull keys it
            # never init-ed as long as the servers hold them
            plan = self._placement(str(k), olist[0])
            if plan[0][1] is None:
                weights = self._rpc(plan[0][0], "pull", str(k),
                                    idempotent=True)[1]
            else:
                replies = self._rpc_scatter(
                    [(s, ("pull", self._subkey(str(k), s, False)))
                     for s, _, _ in plan], idempotent=True)
                weights = _np.concatenate([r[1] for r in replies], axis=0)
            for o in olist:
                o[:] = array(weights)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows, each from its owning server
        (reference: row-sparse PSKV routing in kvstore_dist.h)."""
        from .ndarray.ndarray import NDArray as _ND
        keys, _ = _key_list(key)
        outs = _val_list(out, len(keys))
        if isinstance(row_ids, _ND):
            rids = [row_ids] * len(keys)
        else:
            rids, _ = _key_list(row_ids)
        for k, olist, rid in zip(keys, outs, rids):
            plan = self._placement(str(k), olist[0])
            rows = _np.unique(rid.asnumpy().astype(_np.int64))
            # empty / no-match row_ids no-op with (0,) + row_shape (the
            # dense scatter and row_sparse_array below would otherwise
            # broadcast-error on a bare (0,) value array)
            row_shape = tuple(olist[0].shape[1:])
            if rows.size == 0:
                vals = _np.zeros((0,) + row_shape, _np.float32)
            elif plan[0][1] is None:
                vals = self._rpc(plan[0][0], "pull_rows", str(k), rows,
                                 idempotent=True)[1]
            else:
                calls, kept = [], []
                for s, r0, r1 in plan:
                    mask = (rows >= r0) & (rows < r1)
                    if mask.any():
                        calls.append((s, ("pull_rows",
                                          self._subkey(str(k), s, False),
                                          rows[mask] - r0)))
                        kept.append(rows[mask])
                if calls:
                    replies = self._rpc_scatter(calls, idempotent=True)
                    vals = _np.concatenate([r[1] for r in replies], axis=0)
                    rows = _np.concatenate(kept)
                else:
                    vals = _np.zeros((0,) + row_shape, _np.float32)
                    rows = rows[:0]
            for o in olist:
                if isinstance(o, _mx_sparse.RowSparseNDArray):
                    dst = _mx_sparse.row_sparse_array(
                        (vals, rows), shape=o.shape)
                    o._data, o._indices = dst._data, dst._indices
                else:
                    import jax
                    import jax.numpy as jnp
                    o._data = o._data.at[jnp.asarray(rows)].set(
                        jax.device_put(jnp.asarray(vals),
                                       o.context.jax_device))

    def set_optimizer(self, optimizer):
        self._require_worker()
        self._optimizer = optimizer
        payload = pickle.dumps(optimizer)
        self._rpc_scatter([(s, ("set_optimizer", payload))
                           for s in range(len(self._socks))])

    def barrier(self):
        # one rendezvous point: server 0 tracks the worker group
        self._rpc(0, "barrier", self._rank)

    def server_stats(self):
        """Aggregated {push_count, num_keys} across servers, plus the
        per-server breakdown under "per_server" — the multi-server test
        hook (key accounting proves where shards landed)."""
        self._require_worker()
        per = [r[1] for r in self._rpc_scatter(
            [(s, ("stats",)) for s in range(len(self._socks))],
            idempotent=True)]
        return {"push_count": sum(p["push_count"] for p in per),
                "num_keys": sum(p["num_keys"] for p in per),
                "per_server": per}

    def stop_server(self):
        self._require_worker()
        self._rpc_scatter([(s, ("stop",))
                           for s in range(len(self._socks))])

    # -- checkpoint (worker side) ------------------------------------------

    def save_checkpoint(self, directory):
        """Every server snapshots its addressable shard of weights +
        optimizer state into `directory` (one atomic file per server).
        Used standalone or as a CheckpointManager extra writer — the
        shard files land inside the managed step dir."""
        from .checkpoint.kvshard import save_kv_checkpoint
        self._require_worker()
        return save_kv_checkpoint(self, directory)

    def restore_checkpoint(self, directory):
        """Restore server-side state from `save_checkpoint` files. With
        the same server count each server reloads its own file; under a
        DIFFERENT count the shards are merged host-side and resharded
        for the new topology (checkpoint/kvshard.py)."""
        from .checkpoint.kvshard import restore_kv_checkpoint
        self._require_worker()
        restore_kv_checkpoint(self, directory)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        """Server-side state save (the reference raised here — dist
        kvstores could not save from a worker; the checkpoint subsystem
        lifts that). `fname` becomes a small manifest; the per-server
        shard files live in a `fname + ".kvshards"` sidecar dir on the
        servers' shared filesystem."""
        from .base import atomic_write
        d = fname + ".kvshards"
        files = self.save_checkpoint(d)
        atomic_write(fname, pickle.dumps(
            {"mx_kv_ckpt": 1, "num_servers": self.num_servers,
             "files": [os.path.basename(f) for f in files]},
            protocol=pickle.HIGHEST_PROTOCOL))

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            manifest = pickle.load(f)
        if not (isinstance(manifest, dict) and manifest.get("mx_kv_ckpt")):
            raise MXNetError("%s is not a dist_async optimizer-states "
                             "manifest" % fname)
        self.restore_checkpoint(fname + ".kvshards")
