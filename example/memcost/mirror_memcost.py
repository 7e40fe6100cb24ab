"""Measure the memory/FLOPs trade of gradient mirroring (reference:
example/memcost/ — inception_memcost.py comparing training memory with
`MXNET_BACKWARD_DO_MIRROR`).

Here the measurement is exact and chip-free: the SAME fused
forward+backward program is compiled with mirroring off and on
(`jax.checkpoint` with the dots-saveable policy — matmul/conv outputs
kept, elementwise chains rematerialized, the reference's
recompute-activations rule) and XLA's own `memory_analysis()` /
`cost_analysis()` report peak bytes and FLOPs via
`Executor.program_cost()`.

Measure BEFORE enabling the flag: XLA's scheduler already reuses
buffers aggressively, so on many models (like this weight-dominated
MLP) mirroring changes little — the point of this tool is that the
trade is a number you can read off per model, not folklore.
"""
import argparse
import logging
import os
import subprocess
import sys

CHILD = """
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx

depth, width, batch = %(depth)d, %(width)d, %(batch)d
x = mx.sym.Variable("data")
net = x
for i in range(depth):
    net = mx.sym.Activation(mx.sym.FullyConnected(
        net, num_hidden=width, name="fc%%d" %% i), act_type="tanh")
net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(net, num_hidden=10,
                                                 name="out"),
                           name="softmax")
exe = net.simple_bind(mx.cpu(), grad_req="write",
                      data=(batch, width), softmax_label=(batch,))
stats = exe.program_cost()
print("COST " + json.dumps(stats))
"""


_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     "..", ".."))


def measure(mirror, depth, width, batch):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_BACKWARD_DO_MIRROR="1" if mirror else "0",
               PYTHONPATH=_REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c",
         CHILD % {"depth": depth, "width": width, "batch": batch}],
        capture_output=True, text=True, timeout=900, env=env)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    import json
    for line in proc.stdout.splitlines():
        if line.startswith("COST "):
            return json.loads(line[5:])
    raise RuntimeError("no COST line:\n" + proc.stdout[-1000:])


def main(depth=24, width=512, batch=64):
    off = measure(False, depth, width, batch)
    on = measure(True, depth, width, batch)
    print("%-28s %14s %14s" % ("fwd+bwd program", "mirror OFF", "mirror ON"))
    for key, unit, scale in (("peak_bytes", "MB", 1e6),
                             ("flops", "GFLOP", 1e9)):
        print("%-28s %14.2f %14.2f"
              % ("%s (%s)" % (key, unit), off[key] / scale,
                 on[key] / scale))
    saved = 1 - on["peak_bytes"] / max(off["peak_bytes"], 1)
    extra = on["flops"] / max(off["flops"], 1) - 1
    print("mirroring: %.0f%% less peak memory for %.0f%% more FLOPs"
          % (saved * 100, extra * 100))
    return off, on


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--depth", type=int, default=24)
    ap.add_argument("--width", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    args = ap.parse_args()
    main(args.depth, args.width, args.batch)
