#!/usr/bin/env python
"""CI wire-fuzz smoke (`ci/run.py wire_fuzz_smoke` stage, ISSUE 13).

The safe-wire robustness gate:
  * a fuzz corpus is CAPTURED FROM REAL TRAFFIC — a live gateway serving
    a real client plus a fleet worker joining/heartbeating/rolling over,
    with every encoded payload tapped at the wire seam;
  * >= 10k seeded mutations (bit flips, truncations, splices, header
    bombs) of that corpus + crafted depth/length/shape/dtype bombs feed
    the safe decoder: EVERY outcome must be valid data or the typed
    FrameError (decoder-is-total), and no decode's peak traced
    allocation may exceed the O(frame bytes) budget (caps bind BEFORE
    allocation);
  * ROLLING UPGRADE: a subprocess speaking the previous protocol (old
    hello, old pickle codec — MXNET_SERVING_WIRE=pickle) is served
    BIT-IDENTICALLY by the safe-default gateway;
  * a hostile peer spraying fuzzer output at the LIVE gateway is
    evicted, while `submitted == served + shed + failed` holds for
    everyone else.

Prints one JSON summary line; non-zero exit on any violated contract.
The companion lint half of the stage (tpulint over mxnet_tpu/serving)
runs as a second command in ci/run.py.
"""
import json
import os
import random
import socket
import struct
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.serving import (ModelServer, ServingFrontDoor,  # noqa: E402
                               ServingClient, FleetPool, ReplicaWorker)
from mxnet_tpu.serving import wire, wire_fuzz  # noqa: E402

FUZZ_N = 12000
FUZZ_SEED = 0xC0DEC

# previous-protocol client in a REAL second OS process: the env pins the
# old codec, so this speaks proto 1 byte-for-byte (old hello, pickle)
_OLD_CLIENT = r'''
import json, os, sys
os.environ["MXNET_SERVING_WIRE"] = "pickle"     # the PREVIOUS protocol
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, %(root)r)
import numpy as np
from mxnet_tpu.serving import ServingClient
port = int(sys.argv[1])
cli = ServingClient("127.0.0.1", port)
x = np.frombuffer(bytes.fromhex(sys.argv[2]),
                  dtype=np.float32).reshape(4, 6)
out = np.asarray(cli.predict({"data": x}, model="fz", timeout=60.0)[0])
print(json.dumps({"dtype": str(out.dtype), "shape": list(out.shape),
                  "hex": out.tobytes().hex()}))
cli.close()
'''


def _server(name="fz"):
    rng = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name=name + "_fc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name=name + "_fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes, _, _ = sym.infer_shape(data=(4, 6))
    params = {n: mx.nd.array(rng.normal(0, 0.5, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), shapes)
              if n not in ("data", "softmax_label")}
    srv = ModelServer()
    srv.register(name, sym, params, ctx=mx.cpu(), buckets=(1, 4),
                 max_delay_ms=0.0, warmup_shapes={"data": (4, 6)})
    return srv, params


def capture_corpus():
    """Tap every payload a REAL frontdoor + fleet session encodes."""
    srv, params = _server()
    with wire_fuzz.FrameTap() as tap:
        fd = ServingFrontDoor(srv, port=0).start()
        cli = ServingClient("127.0.0.1", fd.port)
        x = np.arange(24, dtype=np.float32).reshape(4, 6) / 24.0
        for rows in (1, 2, 4):
            cli.predict({"data": x[:rows]}, model="fz", timeout=60.0)
        cli.health()
        cli.list_models()
        # stateful-decode leg: decode request + streamed stok frames +
        # terminal sdone cross the tap (ISSUE 18 stream frames)
        from mxnet_tpu.models.tiny_lm import TinyLMDecodeModel
        from mxnet_tpu.serving import DecodeEngine
        eng = DecodeEngine(**TinyLMDecodeModel().engine_kwargs(),
                           name="fz_lm", num_blocks=16,
                           batch_size=2, max_seq_len=64,
                           prefill_buckets=(16,))
        srv.register_decode("fz_lm", eng)
        cli.decode([3, 1, 4, 1, 5], model="fz_lm", max_new_tokens=6,
                   timeout=60.0)
        # fleet leg: join (hello + probe + joined), heartbeats, rollover
        pool = FleetPool(srv, port=0, heartbeat_s=0.25,
                         connect_deadline_s=2.0).start()
        wsrv, _ = _server()
        worker = ReplicaWorker(("127.0.0.1", pool.port), wsrv, port=0,
                               worker_id="w-fuzz",
                               heartbeat_s=0.25).start()
        assert worker.joined.wait(60.0), "fleet worker never admitted"
        time.sleep(0.6)                      # a few heartbeats
        srv.rollover("fz", params)           # control-channel fan-out
        worker.stop()
        pool.stop()
        cli.close()
        fd.drain(timeout=30.0)
        srv.stop()
    corpus = tap.frames("safe")
    assert len(corpus) >= 20, \
        "traffic tap captured only %d safe frames" % len(corpus)
    return corpus


def fuzz_gate(corpus):
    report = wire_fuzz.run_fuzz(FUZZ_N, seed=FUZZ_SEED, corpus=corpus,
                                track_alloc=True)
    assert report["mutations"] >= 10000, report["mutations"]
    assert report["other_exceptions"] == [], \
        "decoder not total: %s" % report["other_exceptions"][:3]
    assert report["alloc_violations"] == [], \
        "allocation cap violated: %s" % report["alloc_violations"][:3]
    return {"mutations": report["mutations"],
            "frame_errors": report["frame_errors"],
            "decoded_ok": report["decoded_ok"],
            "max_alloc_ratio": report["max_alloc_ratio"],
            "corpus_frames": len(corpus)}


def upgrade_and_spray_gate():
    """One live gateway: a previous-protocol subprocess served
    bit-identically WHILE a hostile peer spraying fuzz gets evicted —
    and the accounting for everyone else stays exact."""
    srv, _ = _server()
    fd = ServingFrontDoor(srv, port=0, evict_threshold=2,
                          evict_cooldown_ms=60000.0).start()
    cli = ServingClient("127.0.0.1", fd.port)
    x = np.arange(24, dtype=np.float32).reshape(4, 6) / 24.0
    want = np.asarray(srv.predict("fz", {"data": x})[0])
    # establish the good client's pooled connection BEFORE the spray:
    # eviction refuses NEW connections from the struck peer host (same
    # loopback here), while established connections keep serving — the
    # "everyone else" the accounting gate is about
    out = cli.predict({"data": x}, model="fz", timeout=60.0)
    assert np.array_equal(np.asarray(out[0]), want)

    # rolling upgrade: previous-protocol subprocess, bit-identity
    proc = subprocess.run(
        [sys.executable, "-c", _OLD_CLIENT % {"root": ROOT},
         str(fd.port), x.tobytes().hex()],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    got = np.frombuffer(bytes.fromhex(rep["hex"]),
                        dtype=rep["dtype"]).reshape(rep["shape"])
    assert got.dtype == want.dtype and np.array_equal(got, want), \
        "previous-protocol client NOT served bit-identically"
    assert fd.stats()["legacy_peers"] >= 1, fd.stats()

    # hostile sprayer: mutated real-shaped frames until eviction
    rng = random.Random(FUZZ_SEED)
    corpus = wire_fuzz.base_corpus()
    deadline = time.monotonic() + 60.0
    sprayed = 0
    while fd.stats()["evictions"] < 1:
        assert time.monotonic() < deadline, \
            "sprayer never evicted: %s" % fd.stats()
        sock = None
        try:
            sock = socket.create_connection(("127.0.0.1", fd.port),
                                            timeout=5.0)
            sock.settimeout(5.0)
            for _ in range(4):
                garbage = wire_fuzz.mutate(rng.choice(corpus), rng)
                sock.sendall(struct.pack("<Q", len(garbage)) + garbage)
                sprayed += 1
            while sock.recv(4096):
                pass
        except OSError:
            pass
        finally:
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
    # everyone else: the safe client keeps being served, exactly
    served = 0
    for _ in range(6):
        out = cli.predict({"data": x}, model="fz", timeout=60.0)
        assert np.array_equal(np.asarray(out[0]), want)
        served += 1
    st = fd.stats()
    assert st["evictions"] >= 1, st
    assert st["submitted"] == st["served"] + st["shed"] + st["failed"], \
        "accounting broke under the spray: %s" % st
    cli.close()
    fd.drain(timeout=30.0)
    srv.stop()
    return {"legacy_peers": st["legacy_peers"],
            "evictions": st["evictions"],
            "refused_evicted": st["refused_evicted"],
            "sprayed_frames": sprayed,
            "negotiated_safe": st["negotiated_safe"],
            "served_during_spray": served,
            "accounting_exact": True}


def main():
    corpus = capture_corpus()
    summary = {
        "fuzz": fuzz_gate(corpus),
        "gateway": upgrade_and_spray_gate(),
    }
    print(json.dumps(summary), flush=True)
    print("wire_fuzz_smoke OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
