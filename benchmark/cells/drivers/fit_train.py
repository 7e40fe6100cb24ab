"""Driver: ``Module.fit(kvstore='tpu_sync')`` on one chip, the way
``train_imagenet.py --benchmark 1`` drives it.

ONE ``fit`` call: it binds, adopts the seeded weights, compiles the fused step
ahead of time, runs ``warm_steps`` untimed steps (the first three are the ones
the reference follows) and then the window, fed by a benchmark-owned iterator
that cycles a pool of host float32 batches and ends the epoch when the clock
runs out. The object the window drives is the object whose first steps were
compared.
"""
import importlib
import time

import numpy as np

from harness.context import Compared, key_from_seed
from harness import flops


class _PoolIter:
    """Cycles a pool of host batches (the script's SyntheticDataIter reuses
    one); stops once the window has been open for ``seconds``."""

    def __init__(self, driver, mx, pool_x, pool_y, data_name, label_name):
        self.d = driver
        self.mx = mx
        self.pool_x, self.pool_y = pool_x, pool_y
        self.batch_size = pool_x[0].shape[0]
        self.provide_data = [mx.io.DataDesc(data_name, pool_x[0].shape,
                                            np.float32)]
        self.provide_label = [mx.io.DataDesc(label_name, pool_y[0].shape,
                                             np.float32)]
        self.issued = 0

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        d = self.d
        with d.ctx.span("bench.iter_next"):
            if d.t_open is not None and \
                    time.monotonic() >= d.t_open + d.ctx.seconds:
                raise StopIteration
            if self.issued > d.warm_steps + 100000:
                raise StopIteration     # the window never opened: a fault
            i = self.issued % len(self.pool_x)
            self.issued += 1
            return self.mx.io.DataBatch(
                data=[self.pool_x[i]], label=[self.pool_y[i]], pad=0)

    __next__ = next


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.batch = int(t["batch_size"])
        self.warm_steps = int(t["warm_steps"])
        self.follow = 3                  # steps the reference follows
        assert self.warm_steps > self.follow
        self.hp = dict(ctx.config["optimizer_params"])
        self.t_open = self.t_close = None
        self.steps = 0
        self.got = {"losses": []}

    # ------------------------------------------------------------------
    def _make_inputs(self):
        ctx, cfg = self.ctx, self.ctx.config
        ref = ctx.reference
        args, aux = ref.init_params(cfg, key_from_seed(ctx.seed, 0))
        self.params0 = {n: np.asarray(v) for n, v in args.items()}
        self.aux0 = {n: np.asarray(v) for n, v in aux.items()}
        rng = np.random.default_rng([ctx.seed, 1])
        side, n = cfg["image_side"], self.batch
        pool = int(ctx.traffic["pool_batches"])
        assert pool >= self.follow, "the compared steps need distinct rows"
        self.pool_x = [rng.random((n, 3, side, side), np.float32) * 2 - 1
                       for _ in range(pool)]
        self.pool_y = [rng.integers(0, cfg["num_classes"], n)
                       .astype(np.float32) for _ in range(pool)]

    def _symbol(self):
        s = self.ctx.config["symbol"]
        fn = getattr(importlib.import_module(s["module"]), s["function"])
        return fn(**s["kwargs"])

    def _on_batch(self, param):
        import jax
        ctx = self.ctx
        with ctx.span("bench.batch_end"):
            k = param.nbatch + 1            # steps dispatched so far
            step = self.mod._fused_step
            if k <= self.follow:
                prob = np.asarray(self.mod._fused_outputs[0]._data,
                                  np.float32)
                y = self.pool_y[k - 1].astype(np.int64)
                self.got["losses"].append(float(
                    -np.log(prob[np.arange(len(y)), y] + 1e-30).mean()))
                if k == 1:
                    self.got["mom1"] = jax.device_get(step.opt_state["mom"])
                if k == self.follow:
                    self.got["params"] = jax.device_get(step.params)
            if k == self.warm_steps:
                jax.block_until_ready(step.params)
                from mxnet_tpu import profiler
                self.compiles_open = profiler.compile_counters()
                self.t_open_wall = time.time()
                self.t_open = time.monotonic()
                ctx.tracer.open_window(self.t_open)
            elif k > self.warm_steps:
                self.steps += 1
                ctx.tracer.tick()
                if param.locals.get("last"):
                    ctx.tracer.stop()
                    jax.block_until_ready(step.params)
                    self.t_close = time.monotonic()

    # ------------------------------------------------------------------
    def run(self):
        import mxnet_tpu as mx
        from mxnet_tpu import profiler
        ctx = self.ctx
        self._make_inputs()
        sym = self._symbol()
        self.mod = mod = mx.mod.Module(sym, context=[mx.tpu(0)])
        it = _PoolIter(self, mx, self.pool_x, self.pool_y, "data",
                       "softmax_label")
        c0 = profiler.compile_counters()
        mod.fit(it, num_epoch=1, kvstore="tpu_sync",
                arg_params={n: mx.nd.array(v) for n, v in self.params0.items()},
                aux_params={n: mx.nd.array(v) for n, v in self.aux0.items()},
                optimizer=ctx.config["optimizer"],
                optimizer_params=self.hp, eval_metric="acc",
                batch_end_callback=self._on_batch)
        if mod._fused_step is None:
            raise RuntimeError("the fused tpu_sync step was not built")
        if self.t_close is None:
            raise RuntimeError("the window never closed")
        c1 = profiler.compile_counters()
        # fit's own thread starts the tracer: what that cost is no part of
        # a traced run's rate (an untraced run has none)
        window_s = self.t_close - self.t_open - ctx.tracer.stall_s
        samples = self.steps * self.batch
        per_sample = flops.train_flops_per_sample(
            ctx.reference.matrix_layers(ctx.config))
        built = c1["sites"].get("train.fused_step", {})
        ctx.log("window", steps=self.steps, samples=samples,
                window_s=window_s, fused_step_compiles=built,
                compute_dtype=str(mod._fused_step.compute_dtype),
                persistent_cache_hits=c1["persistent_cache_hits"])
        return {
            "window_open_wall": self.t_open_wall,
            "end_to_end": {"train_samples_per_s": samples / window_s},
            "attempted": self.steps, "failed": 0,
            "window_s": window_s, "steps": self.steps, "samples": samples,
            "model_flops": per_sample * samples,
            "compile_s_setup": (self.compiles_open["total"]["compile_ms"]
                                - c0["total"]["compile_ms"]) / 1e3,
            "compiles_in_window": (c1["total"]["compiles"]
                                   - self.compiles_open["total"]["compiles"]),
        }

    def release(self):
        """Free the program's state; what the check needs is on the host."""
        self.mod = None

    # ------------------------------------------------------------------
    def check(self, quant=None, rows=None):
        """The first three steps against the plain reference."""
        ctx, lr = self.ctx, float(self.hp["learning_rate"])
        batches = [(self.pool_x[i], self.pool_y[i].astype(np.int32))
                   for i in range(self.follow)]
        if getattr(self, "want", None) is None:
            self.want = ctx.reference.train_reference(
                ctx.config, self.hp, self.params0, batches)
        want, got = self.want, self.got
        if quant is not None or rows is not None:
            # proof runs: the reference in a lower precision (the control)
            # or with part of the batch left out (a fault), in the
            # program's place
            got = ctx.reference.train_reference(
                ctx.config, self.hp, self.params0, batches, quant=quant,
                rows=rows)
        else:
            got = program_side(got, self.params0, lr)
        limits = ctx.traffic["limits"]
        readings, self.leaf_rows = training_readings(got, want, limits)
        ctx.log("check", losses_program=got["losses"],
                losses_reference=want["losses"], **readings)
        self.reported = readings
        return [Compared(name, readings[name], limit)
                for name, limit in limits.items()]


def program_side(got, params0, lr):
    """What the program's state says, as the reference gives its own: the
    first gradient as the optimizer got it (the momentum after one step is
    -lr g) and the parameters' change after the followed steps."""
    return {"losses": got["losses"],
            "grad1": {n: np.asarray(v, np.float64) / -lr
                      for n, v in got["mom1"].items()},
            "change": {n: np.asarray(got["params"][n], np.float64)
                       - params0[n] for n in params0}}


def _norm(x):
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def leaf_table(got, want):
    """Per leaf (‖got‖, ‖want‖, ‖got − want‖)."""
    return {n: (_norm(got[n]), _norm(want[n]),
                _norm(np.asarray(got[n], np.float64) - want[n]))
            for n in want}


def worst_leaf_gap(table, keep):
    """Largest |‖got‖ − ‖want‖| over the kept leaves, against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    med = float(np.median([table[n][1] for n in keep]))
    gaps = {n: abs(table[n][0] - table[n][1]) / max(table[n][1], med, 1e-30)
            for n in keep}
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def median_leaf_gap(table, keep):
    """The median over the kept leaves of |‖got‖ − ‖want‖| / ‖want‖: steady
    from seed to seed where the worst leaf is one small leaf's noise."""
    return float(np.median([abs(table[n][0] - table[n][1])
                            / max(table[n][1], 1e-30) for n in keep]))


def whole_diff(table, keep):
    """‖got − want‖ / ‖want‖ over the kept leaves taken as ONE vector: the
    number that carries direction (a sign flipped reads 2, a state left
    unchanged 1), weighted to the leaves that move most."""
    return float(np.sqrt(sum(table[n][2] ** 2 for n in keep))
                 / max(np.sqrt(sum(table[n][1] ** 2 for n in keep)), 1e-30))


def median_leaf_diff(table, keep):
    return float(np.median([table[n][2] / max(table[n][1], 1e-30)
                            for n in keep]))


def training_readings(got, want, named=()):
    """(readings, per-leaf rows). Every reading by name; the traffic file's
    ``limits`` says which of them are compared (PERF.md section 2 says why
    those), the others are logged beside them. A name ``grad1_diff.<leaf>``
    or ``change3_diff.<leaf>`` among ``named`` reads that one leaf's
    ‖got − want‖ / ‖want‖."""
    grad, change = (leaf_table(got[k], want[k]) for k in ("grad1", "change"))
    leaves = list(want["grad1"])
    # leaves whose gradient is nought to rounding in the reference move by
    # round-off alone: out of the change, by a rule on the gradient
    med = float(np.median([grad[n][1] for n in leaves]))
    moved = [n for n in leaves if grad[n][1] >= 1e-3 * med]
    gap_g, at_g = worst_leaf_gap(grad, leaves)
    gap_c, at_c = worst_leaf_gap(change, moved)
    readings = {
        "grad1_median_gap": median_leaf_gap(grad, leaves),
        "change3_median_gap": median_leaf_gap(change, moved),
        "grad1_diff": whole_diff(grad, leaves),
        "change3_diff": whole_diff(change, moved),
        "grad1_median_diff": median_leaf_diff(grad, leaves),
        "change3_median_diff": median_leaf_diff(change, moved),
        "grad1_worst_leaf_gap": gap_g, "grad1_worst_leaf": at_g,
        "change3_worst_leaf_gap": gap_c, "change3_worst_leaf": at_c,
        "leaves_out_of_change": len(leaves) - len(moved)}
    for name in named:
        kind, _, leaf = name.partition(".")
        if leaf:
            table = {"grad1_diff": grad, "change3_diff": change}[kind]
            readings[name] = table[leaf][2] / max(table[leaf][1], 1e-30)
    for k, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        readings["loss%d_gap" % (k + 1)] = abs(a - b) / abs(b)
    rows = [[n, int(np.size(want["grad1"][n]))] + list(grad[n])
            + list(change[n]) for n in leaves]
    return readings, rows
