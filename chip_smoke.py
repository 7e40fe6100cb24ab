#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the main path still runs on the TPU.

One process, one chip: ResNet-50 through ``Module.fit(kvstore='tpu_sync')``,
the same network behind ``serving.ModelServer``, a GPT-2-small-width
``TransformerDecodeModel`` and a ``MoEMLADecodeModel`` (latent attention,
held experts; published widths, two layers) behind ``DecodeEngine``, and the
Pallas kernels
compiled (not interpreted) — every phase checked against a plain reference
on seeded data. It starts no child process and it refuses any platform but
"tpu"; a failed assertion or exception in any phase is a non-zero exit.

    python chip_smoke.py              # one chip: train, serve, decode (both
                                      # families), kernels
    python chip_smoke.py --chips 4    # four chips: ONLY the cross-chip path
                                      # (dp ResNet-50, dp x tp transformer step)
    python chip_smoke.py --rehearse   # the same phases at tiny sizes, kernels
                                      # in interpret mode, any platform; never
                                      # prints "ok": true

Each phase prints one JSON line (phase, platform, seconds including compile,
compile count, what was compared and the largest difference); the last line
of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The compile cache lives where JAX_COMPILATION_CACHE_DIR says, and otherwise
at the fixed ``<checkout>/.jax_cache``.
"""
import argparse
import contextlib
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))

# published widths; depth, data and weights are the only cuts (seeded)
FULL = {
    "resnet": dict(num_layers=50, num_classes=1000, side=224),
    "train": dict(images=64, batch=32, epochs=5, lr=0.01),
    "serve": dict(buckets=(1, 8, 32), sizes=(1, 3, 8, 20, 32)),
    "decode": dict(vocab_size=50257, num_layers=12, num_heads=12, d_model=768,
                   max_len=1024, buckets=(128, 256), chunk=256,
                   prompts=(100, 230, 450, 700), new_tokens=32,
                   block_size=16, num_blocks=256),
    # openPangu-Ultra-MoE's published widths; one dense + one expert layer,
    # 16 of 256 experts held, an eighth of the vocabulary (3.5 GB in bf16)
    "decode_moe": dict(
        config=dict(hidden_size=7680, num_hidden_layers=2,
                    first_k_dense_replace=1, num_attention_heads=128,
                    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                    qk_rope_head_dim=64, v_head_dim=128,
                    intermediate_size=18432, moe_intermediate_size=2048,
                    n_routed_experts=256, n_shared_experts=1,
                    num_experts_per_tok=8, routed_scaling_factor=2.5,
                    rms_norm_eps=1e-5, rope_theta=25.6e6, vocab_size=19200,
                    experts_held=(0, 16)),
        dtype="bfloat16", max_len=1024, buckets=(128, 256), chunk=256,
        prompts=(100, 230, 450, 700), new_tokens=16, block_size=16,
        num_blocks=256),
    "flash": dict(shape=(4, 8, 4096, 128), dtype="bfloat16",
                  blocks={"stream": (1024, 512), "grid": (512, 512)}),
    "dp4": dict(batch=128, steps=3, lr=0.01),
    "tp": dict(batch=4, seq=1024, steps=2),
}
TINY = {
    "resnet": dict(num_layers=18, num_classes=10, side=32),
    "train": dict(images=16, batch=8, epochs=4, lr=0.01),
    "serve": dict(buckets=(1, 4, 8), sizes=(1, 3, 4, 6, 8)),
    "decode": dict(vocab_size=256, num_layers=2, num_heads=4, d_model=64,
                   max_len=128, buckets=(16, 32), chunk=32,
                   prompts=(10, 25, 45, 70), new_tokens=8,
                   block_size=8, num_blocks=64),
    "decode_moe": dict(
        config=dict(hidden_size=64, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
                    moe_intermediate_size=48, n_routed_experts=8,
                    n_shared_experts=1, num_experts_per_tok=2,
                    routed_scaling_factor=2.5, rms_norm_eps=1e-5,
                    rope_theta=25.6e6, vocab_size=128, experts_held=(2, 4),
                    initializer_range=0.2, block_k=16, step_row_block=2,
                    step_col_blocks=2),
        dtype="float32", max_len=128, buckets=(16, 32), chunk=32,
        prompts=(10, 25, 45, 70), new_tokens=8, block_size=8,
        num_blocks=64),
    "flash": dict(shape=(1, 2, 256, 64), dtype="bfloat16",
                  blocks={"stream": (128, 128), "grid": (128, 128)}),
    "dp4": dict(batch=16, steps=3, lr=0.01),
    "tp": dict(batch=4, seq=64, steps=2),
}
# relative tolerances (difference over the reference's largest magnitude)
TOL_BF16 = 4e-2     # tools/flash_tune.PARITY_DTYPES: bf16 kernels vs f32
TOL_F32 = 1e-3      # the same f32 program at another batch size
TOL_LOSS = 2e-2     # bf16-compute loss vs the fp32 executor's


class Run:
    """What every phase needs to know about this run."""

    def __init__(self, sizes, rehearse, seed):
        import jax
        self.sizes = sizes
        self.rehearse = rehearse
        self.seed = seed
        self.platform = jax.devices()[0].platform
        # rehearsals interpret the kernels; the chip compiles them
        self.interpret = rehearse
        self.kernel_tier = "interpret" if rehearse else "auto"

    def emit(self, phase, t0, c0, **fields):
        from mxnet_tpu import profiler
        c1 = profiler.compile_counters()
        line = {"phase": phase, "platform": self.platform,
                "seconds": round(time.time() - t0, 2),
                "compiles": c1["total"]["compiles"] - c0["total"]["compiles"],
                "persistent_cache_hits": (c1["persistent_cache_hits"]
                                          - c0["persistent_cache_hits"])}
        line.update(fields)
        print(json.dumps(line), flush=True)


@contextlib.contextmanager
def kernel_tier(mode):
    """Pin MXNET_TPU_MESH_KERNEL_TIER while a program is traced (the model
    code resolves its kernel tier from it at trace time)."""
    prev = os.environ.get("MXNET_TPU_MESH_KERNEL_TIER")
    os.environ["MXNET_TPU_MESH_KERNEL_TIER"] = mode
    try:
        yield
    finally:
        if prev is None:
            del os.environ["MXNET_TPU_MESH_KERNEL_TIER"]
        else:
            os.environ["MXNET_TPU_MESH_KERNEL_TIER"] = prev


def rel_err(got, want):
    """max|got - want| over max|want|, in float32 on the host."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite values"
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def compile_with_kernels(run, fn, *args, kernels=1):
    """Compile a jitted function ahead of time and — on the chip, where the
    kernel tier is Mosaic — insist that the Pallas kernels are IN the
    program (a tier that quietly resolved to lax would still be right).
    Returns the executable, so the caller runs what was inspected."""
    compiled = fn.lower(*args).compile()
    if run.platform == "tpu":
        found = compiled.as_text().count("tpu_custom_call")
        assert found >= kernels, \
            "%d Pallas kernel(s) in the compiled program, expected >= %d" \
            % (found, kernels)
    return compiled


def ulp_diff(a, b):
    """Largest distance between two float32 arrays in units in the last
    place, and how many elements differ at all."""
    import numpy as np

    def ordinal(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    d = np.abs(ordinal(a) - ordinal(b))
    return int(d.max()), int((d > 0).sum())


# --------------------------------------------------------------------------
# ResNet: shared set-up for train / serve / dp4
# --------------------------------------------------------------------------

def resnet_symbol(run):
    from mxnet_tpu.models import resnet
    r = run.sizes["resnet"]
    return resnet.get_symbol(num_classes=r["num_classes"],
                             num_layers=r["num_layers"],
                             image_shape="3,%d,%d" % (r["side"], r["side"]))


def resnet_data(run, n):
    import numpy as np
    r = run.sizes["resnet"]
    rng = np.random.RandomState(run.seed)
    x = rng.uniform(-1, 1, (n, 3, r["side"], r["side"])).astype(np.float32)
    y = rng.randint(0, r["num_classes"], (n,)).astype(np.float32)
    return x, y


def resnet_init(run, sym, batch):
    """Seeded initial (arg_params, aux_params) through the normal
    bind + init_params path."""
    import mxnet_tpu as mx
    side = run.sizes["resnet"]["side"]
    mx.random.seed(run.seed)
    mod = mx.mod.Module(sym, context=[mx.tpu(0)])
    mod.bind(data_shapes=[("data", (batch, 3, side, side))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2.0))
    arg, aux = mod.get_params()
    return ({k: v.copy() for k, v in arg.items()},
            {k: v.copy() for k, v in aux.items()})


def executor_forward(sym, arg, aux, x, y, is_train):
    """The plain Executor's fp32 forward on the same weights: the reference
    for the fused step's first loss and for the serving rows."""
    import mxnet_tpu as mx
    exe = sym.simple_bind(mx.tpu(0), grad_req="null", data=x.shape,
                          softmax_label=(x.shape[0],))
    for name, arr in arg.items():
        arr.copyto(exe.arg_dict[name])
    for name, arr in aux.items():
        arr.copyto(exe.aux_dict[name])
    exe.forward(is_train=is_train, data=mx.nd.array(x),
                softmax_label=mx.nd.array(y))
    return exe.outputs[0].asnumpy()


def cross_entropy(prob, y):
    import numpy as np
    return float(-np.log(prob[np.arange(len(y)), y.astype(np.int64)]
                         + 1e-12).mean())


def fit_fused(run, sym, contexts, x, y, batch, epochs, lr, arg, aux):
    """Module.fit(kvstore='tpu_sync') with per-step losses collected by a
    batch-end callback; returns (module, losses)."""
    import mxnet_tpu as mx
    it = mx.io.NDArrayIter(x, y, batch_size=batch, shuffle=False,
                           label_name="softmax_label")
    mod = mx.mod.Module(sym, context=contexts)
    losses = []

    def on_batch(param):
        losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()

    mod.fit(it, num_epoch=epochs, kvstore="tpu_sync",
            arg_params=arg, aux_params=aux, optimizer="sgd",
            optimizer_params={"learning_rate": lr, "momentum": 0.9,
                              "multi_precision": True},
            eval_metric=mx.metric.CrossEntropy(),
            batch_end_callback=on_batch)
    return mod, losses


def built_since(c0, site):
    """Programs one ProgramBuilder site compiled since the `c0` snapshot of
    profiler.compile_counters(): in all, ahead of time, on demand."""
    from mxnet_tpu import profiler
    now = profiler.compile_counters()["sites"].get(site, {})
    then = c0["sites"].get(site, {})
    return {k: now.get(k, 0) - then.get(k, 0)
            for k in ("compiles", "aot", "ondemand")}


def phase_train(run):
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    t0, c0 = time.time(), profiler.compile_counters()
    cfg = run.sizes["train"]
    sym = resnet_symbol(run)
    x, y = resnet_data(run, cfg["images"])
    arg, aux = resnet_init(run, sym, cfg["batch"])

    mod, losses = fit_fused(run, sym, [mx.tpu(0)], x, y, cfg["batch"],
                            cfg["epochs"], cfg["lr"], arg, aux)
    step = mod._fused_step
    assert step is not None, "fused tpu_sync step was dropped silently"
    built = built_since(c0, "train.fused_step")
    assert built == {"compiles": 1, "aot": 1, "ondemand": 0}, \
        "the AOT warm-up must be the one fused-step compile: %s" % built

    leaves = jax.tree_util.tree_leaves((step.params, step.opt_state))
    placed = {d.platform for leaf in leaves for d in leaf.devices()}
    assert placed == {run.platform}, placed
    assert str(step.compute_dtype) == "bfloat16", step.compute_dtype
    masters = {str(v.dtype) for v in step.params.values()}
    assert masters == {"float32"}, masters

    per_epoch = cfg["images"] // cfg["batch"]
    assert len(losses) == per_epoch * cfg["epochs"] >= 8, len(losses)
    assert np.isfinite(losses).all(), losses
    for b in range(per_epoch):
        first, last = losses[b], losses[-per_epoch + b]
        assert last < first, "batch %d: loss %.4f -> %.4f" % (b, first, last)

    prob = executor_forward(sym, arg, aux, x[:cfg["batch"]],
                            y[:cfg["batch"]], is_train=True)
    ref_loss = cross_entropy(prob, y[:cfg["batch"]])
    loss_err = abs(losses[0] - ref_loss) / abs(ref_loss)
    assert loss_err < TOL_LOSS, (losses[0], ref_loss)

    it = mx.io.NDArrayIter(x[:cfg["batch"]], None, batch_size=cfg["batch"])
    pred = mod.predict(it).asnumpy()
    assert mod._serving_engine is not None, \
        "Module.predict fell back from the serving engine to executors"
    assert pred.shape == (cfg["batch"], run.sizes["resnet"]["num_classes"])
    assert np.isfinite(pred).all()
    assert np.allclose(pred.sum(axis=1), 1.0, atol=1e-3)

    run.emit("train", t0, c0, steps=len(losses),
             fused_step_compiles=built,
             losses=[round(v, 4) for v in losses],
             compared="first fused-step loss (bf16 compute) vs plain "
                      "Executor fp32 forward, same seeded weights",
             max_diff=round(loss_err, 6), tolerance=TOL_LOSS,
             param_devices=sorted(placed),
             predict_engine_compiles=mod._serving_engine.compiles)


def phase_serve(run):
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import ModelServer
    t0, c0 = time.time(), profiler.compile_counters()
    cfg = run.sizes["serve"]
    top = cfg["buckets"][-1]
    side = run.sizes["resnet"]["side"]
    sym = resnet_symbol(run)
    x, y = resnet_data(run, top)
    arg, aux = resnet_init(run, sym, top)
    want = executor_forward(sym, arg, aux, x, y, is_train=False)

    srv = ModelServer()
    try:
        srv.register("resnet", sym, arg, aux, ctx=mx.tpu(0),
                     buckets=cfg["buckets"],
                     warmup_shapes={"data": (top, 3, side, side)})
        eng = srv.engine("resnet")
        assert eng.compiles == len(cfg["buckets"]), eng.compiles
        on_chip = run.platform != "cpu"
        assert eng._cache.donate == on_chip, \
            "donation must be on off the CPU backend and only there"

        worst = 0.0
        offset = 0
        for n in cfg["sizes"]:
            rows = np.arange(offset, offset + n) % top
            offset += n
            got = srv.predict("resnet", {"data": x[rows]})[0].asnumpy()
            assert got.shape == want[rows].shape
            worst = max(worst, rel_err(got, want[rows]))
        futs = [(n, srv.predict_async("resnet", {"data": x[:n]}))
                for n in cfg["sizes"]]
        for n, fut in futs:
            out = fut.result_wait(300.0)[0]
            if on_chip:
                # the accelerator branch of _run_padded hands back device
                # arrays and leaves the sync to the reader
                assert isinstance(out, jax.Array), type(out)
                assert {d.platform for d in out.devices()} == {run.platform}
            worst = max(worst, rel_err(out, want[:n]))
        # a caller-owned device buffer survives a donated request, and the
        # request after it still answers with the right rows
        held = jax.device_put(x[:3])
        for _ in range(2):
            got = srv.predict("resnet", {"data": held})[0].asnumpy()
            worst = max(worst, rel_err(got, want[:3]))
        assert np.array_equal(np.asarray(held), x[:3])
        assert worst < TOL_F32, worst

        assert eng.compiles == len(cfg["buckets"]), \
            "a request compiled after warm-up: %d" % eng.compiles
        assert eng.misses == 0, eng.misses
        probed = [b for b in cfg["buckets"] if eng.step_time(b) is not None]
        assert probed, "no bucket ever got a step-time sample"
        stats = {"compiles": eng.compiles, "hits": eng.hits,
                 "misses": eng.misses, "donate": eng._cache.donate,
                 "step_time_buckets": probed}
    finally:
        srv.stop()
    run.emit("serve", t0, c0, requests=2 * len(cfg["sizes"]) + 2,
             compared="ModelServer rows (sync, async, device-resident) vs "
                      "plain Executor forward at batch %d" % top,
             max_diff=round(worst, 8), tolerance=TOL_F32, **stats)


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def phase_decode(run):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu import profiler
    from mxnet_tpu.models.transformer import (
        TransformerConfig, TransformerDecodeModel, transformer_forward)
    from mxnet_tpu.serving.decode import DecodeEngine
    t0, c0 = time.time(), profiler.compile_counters()
    d = run.sizes["decode"]
    cfg = TransformerConfig(vocab_size=d["vocab_size"],
                            num_layers=d["num_layers"],
                            num_heads=d["num_heads"], d_model=d["d_model"],
                            max_len=d["max_len"])
    model = TransformerDecodeModel(cfg, seed=run.seed, flash=run.kernel_tier)
    assert model.flash_engaged, "decode prefill resolved to the lax tier"
    assert max(d["prompts"]) > d["buckets"][-1], "no prompt needs chunking"
    rng = np.random.RandomState(run.seed)
    prompts = [rng.randint(0, d["vocab_size"], (n,)).astype(np.int32)
               for n in d["prompts"]]

    eng = DecodeEngine(**model.engine_kwargs(), name="smoke",
                       block_size=d["block_size"],
                       num_blocks=d["num_blocks"],
                       batch_size=len(prompts), max_seq_len=d["max_len"],
                       prefill_buckets=d["buckets"],
                       prefill_chunk=d["chunk"], default_deadline_ms=None)
    try:
        family = len(d["buckets"]) + 1
        assert sum(eng.program_counts()) == family, eng.program_counts()
        streams = [eng.submit(p, max_new_tokens=d["new_tokens"])
                   for p in prompts]
        outs = [s.result_wait(900.0) for s in streams]
        stats = eng.stats()
        assert sum(eng.program_counts()) == family, \
            "decode compiled while serving: %s" % (eng.program_counts(),)
        assert stats["served"] == len(prompts) and stats["failed"] == 0, stats
        assert stats["prefill_chunks"] >= 2, "chunked prefill never ran"
        if run.platform == "tpu":
            sd = jax.ShapeDtypeStruct
            i32 = np.int32
            text = eng._prefill_b.aot(
                eng._params, eng._cache_spec, sd((d["buckets"][-1],), i32),
                sd((), i32), sd((), i32), sd((eng._mb,), i32),
                sd((), i32)).as_text()
            assert "tpu_custom_call" in text, \
                "no Pallas kernel in the compiled prefill program"
        params = eng._params
    finally:
        eng.stop()

    # teacher-forced reference: ONE causal forward over prompt + generated
    # tokens gives the logits behind every generated token
    n_new = d["new_tokens"]
    assert all(len(o) == n_new for o in outs), [len(o) for o in outs]
    tokens = np.zeros((len(prompts), d["max_len"]), np.int32)
    pos = np.zeros((len(prompts), n_new), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        tokens[i, :len(p)] = p
        tokens[i, len(p):len(p) + n_new] = o
        pos[i] = len(p) - 1 + np.arange(n_new)

    def logits_fn():
        # a new function object per tier: jit caches traces by function
        # identity, and the tier is read from the environment at trace time
        def logits_at(params, tokens, pos):
            logits = transformer_forward(params, tokens, cfg)
            return jnp.take_along_axis(logits, pos[:, :, None], axis=1)
        return jax.jit(logits_at)

    with kernel_tier("0"), jax.default_matmul_precision("highest"):
        ref = np.asarray(logits_fn()(params, tokens, pos))
    with kernel_tier(run.kernel_tier):
        kern = np.asarray(compile_with_kernels(
            run, logits_fn(), params, tokens, pos)(params, tokens, pos))
    logit_err = rel_err(kern, ref)
    assert logit_err < TOL_BF16, logit_err

    # greedy tokens: equal to the reference's, or a near-tie whose margins
    # (reference and kernel tier) are both reported and inside tolerance
    margin_tol = 2 * TOL_BF16 * float(np.abs(ref).max())
    got = np.stack([np.asarray(o, np.int64) for o in outs])
    best = ref.argmax(axis=-1)
    divergences = []
    for i, j in zip(*np.nonzero(got != best)):
        m_ref = float(ref[i, j, best[i, j]] - ref[i, j, got[i, j]])
        m_kern = float(kern[i, j, got[i, j]] - kern[i, j, best[i, j]])
        divergences.append({"seq": int(i), "step": int(j),
                            "ref_margin": round(m_ref, 5),
                            "kernel_tier_margin": round(m_kern, 5)})
        assert m_ref <= margin_tol, divergences[-1]
    run.emit("decode", t0, c0, programs=list(eng.program_counts()),
             prompts=list(d["prompts"]), new_tokens=n_new,
             prefill_chunks=stats["prefill_chunks"],
             flash_engaged=model.flash_engaged,
             compared="kernel-tier logits and DecodeEngine greedy tokens vs "
                      "lax-tier transformer_forward (f32, highest "
                      "precision), teacher-forced",
             max_diff=round(logit_err, 6), tolerance=TOL_BF16,
             tokens_equal=int((got == best).sum()), tokens=int(got.size),
             margin_tolerance=round(margin_tol, 5),
             divergences=divergences[:4])


def phase_decode_moe(run):
    """The latent-attention expert family (models/moe_mla.py) through the
    same DecodeEngine: flash prefill over the latent pool, the absorbed
    step, the held experts' grouped product; greedy tokens teacher-forced
    against the lax-tier full forward on the same weights in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu import profiler
    from mxnet_tpu.models.moe_mla import (MoEMLAConfig, MoEMLADecodeModel,
                                          init_moe_mla, moe_mla_forward)
    from mxnet_tpu.serving.decode import DecodeEngine
    t0, c0 = time.time(), profiler.compile_counters()
    d = run.sizes["decode_moe"]
    cfg = MoEMLAConfig(**d["config"])
    params = init_moe_mla(cfg, jax.random.PRNGKey(run.seed),
                          jnp.dtype(d["dtype"]))
    model = MoEMLADecodeModel(cfg, params=params, flash=run.kernel_tier)
    assert model.flash_engaged, "decode prefill resolved to the lax tier"
    rng = np.random.RandomState(run.seed)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in d["prompts"]]
    eng = DecodeEngine(**model.engine_kwargs(), name="smoke_moe",
                       block_size=d["block_size"],
                       num_blocks=d["num_blocks"],
                       batch_size=len(prompts), max_seq_len=d["max_len"],
                       prefill_buckets=d["buckets"],
                       prefill_chunk=d["chunk"], default_deadline_ms=None)
    try:
        family = len(d["buckets"]) + 1
        streams = [eng.submit(p, max_new_tokens=d["new_tokens"])
                   for p in prompts]
        outs = [s.result_wait(900.0) for s in streams]
        stats = eng.stats()
        assert sum(eng.program_counts()) == family, \
            "decode compiled while serving: %s" % (eng.program_counts(),)
        assert stats["served"] == len(prompts) and stats["failed"] == 0, stats
        assert stats["prefill_chunks"] >= 2, "chunked prefill never ran"
        m = stats["model"]
        assert m["moe_layer_steps"] == stats["steps"] * cfg.num_expert_layers
        assert 0 < m["moe_assignments"] <= (
            (stats["tokens"] - stats["prefills"]) * cfg.num_expert_layers
            * cfg.num_experts_per_tok), m
        assert stats["kv"]["pool_bytes"] == (
            cfg.num_hidden_layers * d["num_blocks"] * d["block_size"]
            * cfg.cache_row_width * jnp.dtype(d["dtype"]).itemsize)
    finally:
        eng.stop()

    n_new = d["new_tokens"]
    p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    ref = []
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: moe_mla_forward(p, cfg, t))
        for p, o in zip(prompts, outs):
            toks = np.concatenate([p, np.asarray(o[:-1], np.int32)])[None]
            ref.append(np.asarray(fwd(p32, toks))[0, len(p) - 1:])
    ref = np.stack(ref)                                  # [n, new, vocab]
    margin_tol = 2 * TOL_BF16 * float(np.abs(ref).max())
    got = np.stack([np.asarray(o, np.int64) for o in outs])
    best = ref.argmax(axis=-1)
    margins = [float(ref[i, j, best[i, j]] - ref[i, j, got[i, j]])
               for i, j in zip(*np.nonzero(got != best))]
    assert all(m <= margin_tol for m in margins), (margins, margin_tol)
    run.emit("decode_moe", t0, c0, programs=list(eng.program_counts()),
             prompts=list(d["prompts"]), new_tokens=n_new,
             prefill_chunks=stats["prefill_chunks"],
             flash_engaged=model.flash_engaged, model=stats["model"],
             pool_bytes=stats["kv"]["pool_bytes"],
             compared="DecodeEngine greedy tokens vs the lax-tier "
                      "moe_mla_forward (f32, highest precision), "
                      "teacher-forced: the reference's margin of its own "
                      "best over a served token that differs",
             max_diff=round(max(margins, default=0.0), 6),
             tolerance=round(margin_tol, 5),
             tokens_equal=int((got == best).sum()), tokens=int(got.size),
             divergent_margins=[round(m, 5) for m in margins[:4]])


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def flash_parity(run):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.kernels.flash_attention import (blockwise_attention,
                                                   flash_attention)
    f = run.sizes["flash"]
    B, H, S, D = f["shape"]
    dtype = jnp.dtype(f["dtype"])
    rng = np.random.RandomState(run.seed)
    q, k, v, do = (jnp.asarray(rng.normal(0, 1, (B, H, S, D)), dtype)
                   for _ in range(4))

    # `do` rides in as an argument: closed over, its 32 MiB would be baked
    # into every executable — and into every persistent-cache entry
    def ref_loss(q, k, v, do):
        out, _ = blockwise_attention(q, k, v, causal=True,
                                     sm_scale=1.0 / np.sqrt(D), block_k=512)
        return jnp.sum(out * do), out

    with jax.default_matmul_precision("highest"):
        (_, out_r), grads_r = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(
                *(t.astype(jnp.float32) for t in (q, k, v, do)))
    report = {}
    for variant, (bq, bk) in f["blocks"].items():
        def loss(q, k, v, do):
            out = flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk, use_pallas=not run.interpret,
                                  interpret=run.interpret, variant=variant)
            return jnp.sum(out.astype(jnp.float32)
                           * do.astype(jnp.float32)), out
        fn = compile_with_kernels(
            run, jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                            has_aux=True)),
            q, k, v, do, kernels=3)     # forward, dq, dk/dv
        (_, out), grads = fn(q, k, v, do)
        errs = {"out": rel_err(out, out_r)}
        for name, g, g_r in zip(("dq", "dk", "dv"), grads, grads_r):
            errs[name] = rel_err(g, g_r)
        assert max(errs.values()) < TOL_BF16, (variant, errs)
        report[variant] = {n: round(e, 6) for n, e in errs.items()}
    return report


def opt_update_parity(run):
    """fused_update_step's kernel tier against its lax leaf over the whole
    ResNet parameter tree: what kernels/opt_update.py calls the parity
    contract, measured in ulp on this backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.kernels.opt_update import (_kernel_eligible,
                                              fused_update_step)
    side = run.sizes["resnet"]["side"]
    sym = resnet_symbol(run)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 3, side, side),
                                       softmax_label=(1,))
    shapes = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    rng = np.random.RandomState(run.seed)

    def tree(scale, positive=False):
        out = {}
        for n, s in shapes.items():
            a = rng.normal(0, scale, s).astype(np.float32)
            out[n] = jnp.asarray(np.abs(a) if positive else a)
        return out

    params, grads = tree(0.05), tree(0.5)
    states = {
        "sgd": {"mom": tree(0.01)},
        "adam": {"m": tree(0.01), "v": tree(1e-3, positive=True),
                 "t": jnp.asarray(3, jnp.int32)},
    }
    hps = {"sgd": {"lr": 0.05, "momentum": 0.9},
           "adam": {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}}
    eligible = sum(1 for p in params.values() if _kernel_eligible(p))
    assert eligible > len(params) // 3, (eligible, len(params))
    report = {"kernel_leaves": eligible, "leaves": len(params)}
    for opt in ("sgd", "adam"):
        def update(p, s, g, use_kernel):
            return fused_update_step(
                opt, hps[opt], p, s, g, rescale=1.0 / 32, wd=1e-4,
                use_pallas=use_kernel and not run.interpret,
                interpret=use_kernel and run.interpret)
        kern = compile_with_kernels(
            run, jax.jit(lambda p, s, g: update(p, s, g, True)),
            params, states[opt], grads, kernels=eligible)
        lax_ = jax.jit(lambda p, s, g: update(p, s, g, False))
        got = jax.tree_util.tree_leaves(kern(params, states[opt], grads))
        want = jax.tree_util.tree_leaves(lax_(params, states[opt], grads))
        worst, differing, total = 0, 0, 0
        for a, b in zip(got, want):
            if a.dtype != jnp.float32:
                assert np.array_equal(np.asarray(a), np.asarray(b))
                continue
            assert np.isfinite(np.asarray(a)).all()
            u, n = ulp_diff(np.asarray(a), np.asarray(b))
            worst, differing, total = max(worst, u), differing + n, \
                total + a.size
        report[opt] = {"max_ulp": worst, "differing": differing,
                       "elements": total}
    return report


def pallas_rtc_parity(run):
    """rtc.PallasKernel compiles its kernel where the arguments live on a
    TPU and interprets it elsewhere — one elementwise kernel through that
    gate, on chip-context arrays and on default-context (host) ones."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.rtc import PallasModule

    def axpy(x_ref, y_ref, o_ref):
        o_ref[...] = 2.0 * x_ref[...] + y_ref[...]

    kern = PallasModule().add_kernel(
        "axpy", axpy, lambda x, y: jax.ShapeDtypeStruct(x.shape, x.dtype))
    rng = np.random.RandomState(run.seed)
    x = rng.normal(0, 1, (256, 128)).astype(np.float32)
    y = rng.normal(0, 1, (256, 128)).astype(np.float32)
    for ctx in (mx.tpu(0), mx.cpu()):
        out = kern.launch([mx.nd.array(x, ctx=ctx), mx.nd.array(y, ctx=ctx)])
        assert np.array_equal(out.asnumpy(), 2.0 * x + y), ctx
    return True


def phase_kernels(run):
    from mxnet_tpu import profiler
    t0, c0 = time.time(), profiler.compile_counters()
    flash = flash_parity(run)
    opt = opt_update_parity(run)
    rtc = pallas_rtc_parity(run)
    run.emit("kernels", t0, c0,
             compared="flash_attention fwd+bwd (stream, grid; %s %s causal) "
                      "vs blockwise_attention in f32; fused_update_step "
                      "kernel tier vs its lax leaf on the ResNet tree"
                      % (run.sizes["flash"]["dtype"],
                         list(run.sizes["flash"]["shape"])),
             max_diff=max(max(v.values()) for v in flash.values()),
             tolerance=TOL_BF16, flash_rel_err=flash, opt_update_ulp=opt,
             rtc_pallas_kernel=rtc,
             compiled=not run.interpret)


# --------------------------------------------------------------------------
# --chips 4: only what exists across chips, and what it is compared with
# --------------------------------------------------------------------------

def phase_dp4(run):
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.analysis.program_audit import parse_hlo_collectives
    t0, c0 = time.time(), profiler.compile_counters()
    cfg = run.sizes["dp4"]
    devices = jax.devices()
    sym = resnet_symbol(run)
    x, y = resnet_data(run, cfg["batch"])
    arg, aux = resnet_init(run, sym, cfg["batch"])

    def fit(n):
        return fit_fused(run, sym, [mx.tpu(i) for i in range(n)], x, y,
                         cfg["batch"], cfg["steps"], cfg["lr"], arg, aux)

    mod4, loss4 = fit(4)
    step = mod4._fused_step
    assert step is not None, "fused tpu_sync step was dropped silently"
    assert dict(step.mesh.shape) == {"dp": 4}, dict(step.mesh.shape)
    for name, p in step.params.items():
        assert p.sharding.device_set == set(devices), \
            "%s lives on %s" % (name, p.sharding.device_set)
        assert len({s.device for s in p.addressable_shards}) == 4
    staged = jax.device_put(x, step._batch_shard)
    shard_devs = {s.device for s in staged.addressable_shards}
    assert shard_devs == set(devices), shard_devs
    assert {s.data.shape[0] for s in staged.addressable_shards} \
        == {cfg["batch"] // 4}, "batch is not split over dp"

    # the compiled step itself (the warm-up's executable, no new compile):
    # which collectives the partitioner put in, and over which mesh axis
    exe = step._step.aot(*step.abstract_step_args())
    plan = step.comm_plan()
    table = {}
    for c in parse_hlo_collectives(exe.as_text(), step.mesh):
        assert plan.allows(c["op"], c["axis"]) is not None, \
            "stray collective in the fused step: %s" % c
        key = "%s@%s" % (c["op"], c["axis"])
        table[key] = table.get(key, 0) + 1
    assert any(k in table for k in ("all-reduce@dp", "reduce-scatter@dp")), \
        "no gradient reduction over dp in the compiled step: %s" % table
    params4, aux4 = mod4.get_params()

    mod1, loss1 = fit(1)
    assert dict(mod1._fused_step.mesh.shape) == {"dp": 1}
    params1, aux1 = mod1.get_params()
    assert np.isfinite(loss4).all() and np.isfinite(loss1).all()
    loss_err = max(abs(a - b) / abs(b) for a, b in zip(loss4, loss1))
    assert loss_err < TOL_LOSS, (loss4, loss1)
    # values of a handful of arrays, and — sharper — the UPDATE the three
    # steps made to the classifier weight. A gradient reduction over dp that
    # was missing or mis-scaled puts that at 0.75 or worse; bf16 rounding
    # alone puts it at 0.07 (v5e 2x2, PR 22) to 0.3 (small batches), because
    # at a fresh initialisation on random labels the gradient is mostly
    # cancellation. Early layers' updates are rounding noise outright (two
    # bf16 runs, or bf16 and fp32 on ONE device, differ by ~1.0 there; in
    # fp32 dp=4 and dp=1 agree to 1e-4 at the classifier) — reported, not held.
    all4, all1, init = ({**params4, **aux4}, {**params1, **aux1},
                        {**arg, **aux})
    weights = sorted(n for n in params4 if n.endswith("_weight"))
    picked = ["conv0_weight", weights[len(weights) // 2], "fc1_weight",
              "bn1_gamma", "bn1_moving_mean", "bn1_moving_var"]
    worst = max(rel_err(all4[n].asnumpy(), all1[n].asnumpy())
                for n in picked)
    assert worst < TOL_BF16, worst

    def update_err(n):
        d4 = all4[n].asnumpy() - init[n].asnumpy()
        d1 = all1[n].asnumpy() - init[n].asnumpy()
        return float(np.linalg.norm(d4 - d1) / np.linalg.norm(d1))

    updates = {n: round(update_err(n), 5) for n in picked[:4]}
    assert updates["fc1_weight"] < 0.5, updates
    run.emit("dp4_train", t0, c0, steps=len(loss4),
             losses_4chip=[round(v, 4) for v in loss4],
             losses_1chip=[round(v, 4) for v in loss1],
             compared="ResNet tpu_sync over 4 devices vs the same seeded "
                      "global batch on one: per-step loss and %d arrays"
                      % len(picked),
             max_diff=round(max(loss_err, worst), 6), tolerance=TOL_BF16,
             loss_rel_err=round(loss_err, 6), param_rel_err=round(worst, 6),
             update_l2_rel_err=updates, param_devices=4, batch_shard_rows=cfg["batch"] // 4,
             collectives=table)


def phase_tp(run):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from mxnet_tpu import profiler
    from mxnet_tpu.kernels.flash_attention import flash_attention
    from mxnet_tpu.models.transformer import (
        TransformerConfig, init_transformer, transformer_loss,
        transformer_sharding_rules)
    from mxnet_tpu.parallel.mesh_kernels import flash_attention_mesh
    from mxnet_tpu.parallel.sharded_step import ShardedTrainStep
    t0, c0 = time.time(), profiler.compile_counters()
    d, t = run.sizes["decode"], run.sizes["tp"]
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices).reshape(2, 2), ("dp", "tp"))
    cfg = TransformerConfig(vocab_size=d["vocab_size"],
                            num_layers=d["num_layers"],
                            num_heads=d["num_heads"], d_model=d["d_model"],
                            max_len=d["max_len"], attn_impl="full",
                            block_k=min(512, t["seq"]))
    params = init_transformer(cfg, jax.random.PRNGKey(run.seed))
    rng = np.random.RandomState(run.seed)
    toks = rng.randint(0, d["vocab_size"],
                       (t["batch"], t["seq"] + 1)).astype(np.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    with kernel_tier(run.kernel_tier):
        ref_loss = float(jax.jit(
            lambda p, b: transformer_loss(p, b["tokens"], b["targets"], cfg,
                                          train=False))(params, batch))
        step = ShardedTrainStep(
            lambda p, b: transformer_loss(p, b["tokens"], b["targets"], cfg,
                                          mesh=mesh, train=False),
            mesh, transformer_sharding_rules(cfg, mesh), optimizer="adam",
            lr=1e-3, grad_clip=1.0)
        step.init(params)
        step.warmup(batch)
        losses = [float(step(batch)) for _ in range(t["steps"])]
    built = built_since(c0, "train.sharded_step")
    assert built == {"compiles": 1, "aot": 1, "ondemand": 0}, built
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    loss_err = abs(losses[0] - ref_loss) / abs(ref_loss)
    assert loss_err < TOL_LOSS, (losses[0], ref_loss)
    wq = step.params["layers"]["wq"]
    assert wq.sharding.device_set == set(devices)
    assert {s.data.shape for s in wq.addressable_shards} \
        == {wq.shape[:2] + (wq.shape[2] // 2,)}, "wq is not split over tp"
    if run.platform == "tpu":
        prog = step._step_fn.lookup(step.params, step.opt_state,
                                    jax.tree_util.tree_map(
                                        lambda a: jax.device_put(
                                            a, step._batch_sharding), batch))
        assert "tpu_custom_call" in prog.as_text(), \
            "no Pallas kernel inside the sharded step's shard_map island"

    # the island against the one-device kernel: same tier, so bit-identical
    H, Dh = cfg.num_heads, cfg.d_model // cfg.num_heads
    q, k, v = (jnp.asarray(rng.normal(0, 1, (t["batch"], H, t["seq"], Dh)),
                           jnp.bfloat16) for _ in range(3))
    blk = min(512, t["seq"])
    tier = dict(use_pallas=not run.interpret, interpret=run.interpret)
    solo = flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk,
                           **tier)
    isle = jax.jit(lambda q, k, v: flash_attention_mesh(
        q, k, v, mesh, causal=True, block_q=blk, block_k=blk,
        require_kernel=True, **tier))(q, k, v)
    assert len({s.device for s in isle.addressable_shards}) == 4
    island_err = rel_err(isle, solo)
    assert island_err == 0.0, island_err
    run.emit("tp_train", t0, c0, mesh={"dp": 2, "tp": 2},
             losses=[round(v, 4) for v in losses],
             compared="ShardedTrainStep first loss on dp=2 x tp=2 vs the "
                      "one-device transformer_loss; flash_attention_mesh "
                      "(require_kernel) vs the one-device kernel",
             max_diff=round(max(loss_err, island_err), 6),
             tolerance=TOL_LOSS, loss_rel_err=round(loss_err, 6),
             island_rel_err=island_err,
             sharded_step_compiles=built)


PHASES = {1: (phase_train, phase_serve, phase_decode, phase_decode_moe,
              phase_kernels),
          4: (phase_dp4, phase_tp)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip phases")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, interpreted kernels, any platform; "
                         "never a result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(_HERE, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        sys.exit("chip_smoke: platform is %r, not 'tpu' — nothing to prove "
                 "here (rehearse with --rehearse)" % dev.platform)
    if len(devices) != args.chips:
        sys.exit("chip_smoke: --chips %d but JAX reports %d device(s)"
                 % (args.chips, len(devices)))
    sys.path.insert(0, _HERE)
    import mxnet_tpu  # noqa: F401 — without the package, fail before any output
    run = Run(TINY if args.rehearse else FULL, args.rehearse, args.seed)
    print(json.dumps({"phase": "start", "platform": dev.platform,
                      "device_kind": dev.device_kind, "count": len(devices),
                      "rehearse": args.rehearse, "jax": jax.__version__,
                      "compile_cache": jax.config.jax_compilation_cache_dir}),
          flush=True)
    for phase in PHASES[args.chips]:
        phase(run)
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "platform": dev.platform,
                          "count": len(devices)}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
