#!/usr/bin/env python
"""Benchmark harness — prints one JSON result line.

Headline metric mirrors the reference's `benchmark_score.py` (docs/faq/perf.md):
ResNet-50 inference images/sec at batch 32, vs the reference's best published
single-GPU number (P100, 713.17 img/s, docs/faq/perf.md:137-144). The `extra`
field carries fused train-step throughputs (fp32 + bf16, the analog of
`train_imagenet.py` numbers, docs/faq/perf.md:154-185), a Pallas flash-
attention TFLOP/s figure, and `vs_jax_flax` — our fused step vs an idiomatic
plain-Flax ResNet-50 train step on the SAME chip (tools/flax_baseline.py),
the honest north-star ratio from BASELINE.json.

Process layout (one process per chip):
  * The parent never imports jax, so it never holds the chip. Every
    measurement runs in a child process, one at a time.
  * Each phase (infer / train / bf16 / flash / flax-baseline / ...) is its
    OWN child with its OWN budget.
  * The run happens on the platform JAX gives it and never switches platform
    itself: unless the caller exported JAX_PLATFORMS=cpu, a platform other
    than "tpu" is refused. A failed phase makes the exit code non-zero.
  * Children share a persistent XLA compile cache: where
    JAX_COMPILATION_CACHE_DIR is set, that directory; otherwise the fixed
    <checkout>/.jax_cache.
"""
import json
import os
import subprocess
import sys
import time

BASELINE_INFER_P100 = 713.17   # ResNet-50 score b32, docs/faq/perf.md:137-144
BASELINE_TRAIN_P100 = 181.53   # ResNet-50 train b32, docs/faq/perf.md:178-185

PHASE_BUDGET_S = {               # per-phase child timeouts (first-compile heavy)
    "infer": 900, "train_fp32": 800, "train_bf16": 600,
    "jax_baseline": 700, "flash": 700, "io_train": 600,
    "infer_int8": 600, "train_big_batch": 900, "flash_parity": 500,
    "cost": 600, "serving": 600, "serving_sla": 300,
    "frontdoor": 300, "fleet": 300, "decode": 300, "fault_recovery": 300,
    "compile_cache": 300, "train_chaos": 300,
}
TOTAL_DEADLINE_S = int(os.environ.get("BENCH_DEADLINE_S", "3300"))
_HERE = os.path.dirname(os.path.abspath(__file__)) or "."


def _platform_refusal(platform):
    """Why a run on `platform` is refused, or None when it may proceed: the
    chip is what this harness measures, so anything but "tpu" needs the
    caller's explicit JAX_PLATFORMS=cpu (the CI smoke exports it)."""
    if platform == "tpu" or os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    return ("bench: platform is %r, not 'tpu', and JAX_PLATFORMS=cpu was not "
            "exported; refusing to measure another platform in the chip's "
            "place" % platform)


def _child_env(host_side):
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(_HERE, ".jax_cache"))
    # cache aggressively: even fast-compiling entries help a later child
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    if host_side:
        sys.path.insert(0, _HERE)
        from ci.envutil import cpu_mesh_env
        env = cpu_mesh_env(1, base=env)
    return env


def _run_child(phase, host_side, timeout_s):
    """Run `bench.py --phase <phase>` in a fresh process; return (dict|None, err)."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", phase],
            env=_child_env(host_side), capture_output=True, text=True,
            timeout=timeout_s, cwd=_HERE)
    except subprocess.TimeoutExpired:
        return None, "timeout after %ds" % timeout_s
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except ValueError:
                continue
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-6:]
    return None, "rc=%d: %s" % (proc.returncode, " | ".join(tail))


def main():
    t0 = time.time()
    extra = {}
    errors = []

    def remaining():
        return TOTAL_DEADLINE_S - (time.time() - t0)

    # 1) which platform did JAX give us, and what does the device call
    #    itself? Asked in a child: the parent must not take the chip. The
    #    child refuses a platform the caller did not ask for (as every
    #    phase child would), so a refusal ends the run here.
    probe, err = _run_child("probe", False, 300)
    if probe is None:
        sys.exit("bench: no usable backend: %s" % err)
    extra["platform"] = probe["platform"]
    extra["device_kind"] = probe.get("device_kind", "")

    # 2) measurement phases, each in its own budgeted child
    phases = ["infer", "train_fp32", "train_bf16", "jax_baseline", "flash",
              "io_train", "infer_int8", "train_big_batch", "flash_parity",
              "cost", "serving", "frontdoor", "fleet", "decode",
              "fault_recovery", "compile_cache", "train_chaos"]
    if os.environ.get("BENCH_SKIP_BF16"):
        phases.remove("train_bf16")
    # "cost" is analytic (lowered-HLO accounting, no execution).
    # "compile_cache" measures HOST-side compile wall-time and
    # process-restart cold start (its acceptance gate is defined on the
    # CPU host — ISSUE 14). "train_chaos" gates kill/resume SEMANTICS
    # (bit-parity, skip accounting) over subprocess fits whose elastic
    # variant needs a 4-device mesh (ISSUE 15). All three are defined on
    # the CPU and run in a child pinned there.
    host_phases = ("cost", "compile_cache", "train_chaos")
    results = {}
    for phase in phases:
        budget = min(PHASE_BUDGET_S[phase], max(0, int(remaining())))
        if budget < 90:
            errors.append("%s: skipped (deadline)" % phase)
            continue
        res, err = _run_child(phase, phase in host_phases, budget)
        if res is None:
            errors.append("%s: %s" % (phase, err))
            continue
        if phase == "cost":
            # lowered-HLO accounting: platform-independent by design
            res["_platform"] = "analytic"
        elif phase in host_phases:
            # host-measured by design: the label must say so even when
            # the run's backend is TPU
            res["_platform"] = "cpu"
        else:
            res["_platform"] = extra["platform"]
        results[phase] = res

    # 3) merge
    infer = results.get("infer", {})
    value = infer.get("img_per_sec", 0.0)
    for phase in phases:
        if phase != "infer":
            extra.update({k: v for k, v in results.get(phase, {}).items()
                          if not k.startswith("_")})
    if "train_img_per_sec" in extra:
        extra["train_vs_baseline"] = round(
            extra["train_img_per_sec"] / BASELINE_TRAIN_P100, 3)
    # the honest ratio: our best fused step vs plain Flax on the same chip.
    # vs_jax_flax is ALWAYS reported: either the ratio or a typed
    # `vs_jax_flax_skipped` reason, so a consumer can tell "regressed and
    # hidden" from "not computable this run".
    flax_ips = extra.get("jax_train_img_per_sec")
    if "train_bf16_img_per_sec" in extra:
        ours, ours_dtype = extra["train_bf16_img_per_sec"], "bfloat16"
    else:
        ours, ours_dtype = extra.get("train_img_per_sec"), "float32"
    if flax_ips and ours:
        extra["vs_jax_flax"] = round(ours / flax_ips, 3)
        if ours_dtype != extra.get("jax_baseline_dtype"):
            # dtypes diverged (e.g. the bf16 phase failed): label the
            # numerator so the ratio can't masquerade as like-for-like
            extra["vs_jax_flax_ours_dtype"] = ours_dtype
    elif not flax_ips and not ours:
        extra["vs_jax_flax_skipped"] = (
            "missing-both: neither the fused train phases nor jax_baseline "
            "produced a throughput this run")
    elif not flax_ips:
        extra["vs_jax_flax_skipped"] = (
            "missing-denominator: jax_baseline (flax train step) "
            "produced no jax_train_img_per_sec")
    else:
        extra["vs_jax_flax_skipped"] = (
            "missing-numerator: no train_img_per_sec / "
            "train_bf16_img_per_sec from the fused train phases")
    if errors:
        extra["errors"] = "; ".join(errors)[-800:]
    extra["bench_seconds"] = round(time.time() - t0, 1)
    print(json.dumps({"metric": "resnet50_inference_batch32_img_per_sec",
                      "value": round(value, 2), "unit": "images/sec",
                      "vs_baseline": round(value / BASELINE_INFER_P100, 3),
                      "extra": extra}), flush=True)
    if errors:
        sys.exit(1)


# ---------------------------------------------------------------- phases --

def _phase_probe():
    import jax
    d = jax.devices()[0]
    n = jax.numpy.ones((8, 8))
    jax.block_until_ready(n @ n)  # backend actually executes, not just lists
    return {"platform": d.platform, "device_kind": getattr(d, "device_kind", "")}


def _timed_score_loop(exe, batch, side, n_iter, seed=0):
    """Shared scoring protocol for the fp32 and int8 inference phases.

    Pre-stages DISTINCT device batches and cycles through them: per-step
    host->device copies would measure the link, not the chip, and the
    reference score benchmark also measures compute only. 3-iter warmup,
    wait_to_read-bounded timing."""
    import numpy as np
    import jax
    from mxnet_tpu.ndarray.ndarray import _new_from_jax
    rng = np.random.RandomState(seed)
    datas = [_new_from_jax(jax.device_put(rng.uniform(
        -1, 1, (batch, 3, side, side)).astype(np.float32)))
        for _ in range(n_iter)]
    jax.block_until_ready([d._data for d in datas])
    for _ in range(3):  # warmup: compile + steady-state
        exe.forward(is_train=False, data=datas[0])
    exe.outputs[0].wait_to_read()
    tic = time.time()
    for d in datas:
        exe.forward(is_train=False, data=d)
    exe.outputs[0].wait_to_read()
    return round(batch * n_iter / (time.time() - tic), 2)


def _phase_infer():
    """Reference benchmark_score.py analog: jitted forward, random params."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    platform = jax.devices()[0].platform
    batch, n_iter = 32, (30 if platform != "cpu" else 3)
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224")
    exe = sym.simple_bind(mx.tpu(0), grad_req="null",
                          data=(batch, 3, 224, 224), softmax_label=(batch,))
    rng = np.random.RandomState(0)
    for name, arr in exe.arg_dict.items():
        if name not in ("data", "softmax_label"):
            arr[:] = rng.normal(0, 0.01, arr.shape).astype(np.float32)
    return {"img_per_sec": _timed_score_loop(exe, batch, 224, n_iter)}


def _fused_train_ips(compute_dtype=None, batch=32, n_iter=None):
    """Fused train step (fwd+bwd+SGD in ONE jitted program, donated buffers)
    on a 1-device mesh — the `train_imagenet.py --kv-store tpu_sync` path.
    compute_dtype='bfloat16' additionally exercises the mixed-precision
    path (fp32 master weights, reference mp_sgd analog)."""
    import numpy as np
    import jax
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.mesh import data_parallel_mesh
    from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
    platform = jax.devices()[0].platform
    if n_iter is None:
        n_iter = 15 if platform != "cpu" else 2
    mesh = data_parallel_mesh(jax.devices()[:1])
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224")
    step = DataParallelTrainStep(sym, mesh, lr=0.05, momentum=0.9,
                                 data_names=("data",),
                                 label_names=("softmax_label",),
                                 compute_dtype=compute_dtype)
    step.init({"data": (batch, 3, 224, 224), "softmax_label": (batch,)})
    rng = np.random.RandomState(0)
    batches = []   # distinct device-staged batches (see _phase_infer for why)
    for _ in range(4):
        b = {"data": rng.uniform(-1, 1,
                                 (batch, 3, 224, 224)).astype(np.float32),
             "softmax_label": rng.randint(0, 1000,
                                          (batch,)).astype(np.float32)}
        batches.append({k: jax.device_put(v, step._batch_shard)
                        for k, v in b.items()})
    jax.block_until_ready(batches)
    key = jax.random.PRNGKey(0)
    for _ in range(2):  # warmup
        out = step(batches[0], rng=key)
    jax.block_until_ready(out)
    tic = time.time()
    for i in range(n_iter):
        out = step(batches[i % len(batches)], rng=key)
    jax.block_until_ready(out)
    return round(batch * n_iter / (time.time() - tic), 2)


def _phase_train_fp32():
    return {"train_img_per_sec": _fused_train_ips()}


def _phase_train_bf16():
    return {"train_bf16_img_per_sec": _fused_train_ips("bfloat16")}


def _phase_train_big_batch():
    """bf16 fused train at batch 256 — ours AND plain Flax in the same
    child, same chip, for an honest large-batch ratio. The reference's
    published numbers stop at batch 32 (2016-era GPU memory); a v5e's
    MXU only saturates at larger batches, so this is where the TPU-first
    design shows headroom rather than parity. TPU-only: a b256
    ResNet-50 on the CPU would burn minutes for a number nobody reads."""
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform == "cpu":
        return {}
    ours = _fused_train_ips("bfloat16", batch=256, n_iter=8)
    sys.path.insert(0, _HERE)
    from tools import flax_baseline
    flax_ips = flax_baseline.bench(batch=256, n_iter=8,
                                   compute_dtype=jnp.bfloat16)
    return {"train_bf16_b256_img_per_sec": ours,
            "jax_train_b256_img_per_sec": round(flax_ips, 2),
            "vs_jax_flax_b256": round(ours / flax_ips, 3)}


def _phase_jax_baseline():
    """Plain flax.linen ResNet-50 train step on the same chip — the honest
    yardstick (BASELINE.json: >=70% of reference JAX/Flax img/s/chip).
    bf16 compute on TPU to match our best fused-step config; fp32 on CPU."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, _HERE)
    from tools import flax_baseline
    on_tpu = jax.devices()[0].platform != "cpu"
    ips = flax_baseline.bench(
        batch=32, n_iter=15 if on_tpu else 2,
        compute_dtype=jnp.bfloat16 if on_tpu else None)
    return {"jax_train_img_per_sec": round(ips, 2),
            "jax_baseline_dtype": "bfloat16" if on_tpu else "float32"}


def _tpu_roofline_tflops(device_kind, flops, ideal_bytes):
    """Roofline ceiling (TFLOP/s) for a kernel of this arithmetic
    intensity on a recognized chip; None when the chip is unknown (a
    device that is not in the table gets no default peak)."""
    peaks = {  # bf16 peak TFLOP/s, HBM GB/s (public chip specs)
        "v5 lite": (197.0, 819.0), "v5e": (197.0, 819.0),
        "v5p": (459.0, 2765.0), "v4": (275.0, 1228.0),
        "v3": (123.0, 900.0), "v2": (45.0, 700.0),
    }
    kind = (device_kind or "").lower()
    for key, (peak, bw) in peaks.items():
        if key in kind:
            intensity = flops / max(ideal_bytes, 1.0)     # FLOP per byte
            return min(peak, bw * intensity / 1e3)        # GB/s -> TFLOP/s
    return None


def _phase_flash():
    """Fused Pallas flash-attention kernel (non-interpret on TPU): bf16
    causal attention [B=4, H=8, S=4096, D=128] TFLOP/s. New TPU-native
    capability — the reference (2018) has no attention op; this is the
    kernel the long-context stack (ring attention) is built on."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.flash_attention import (flash_attention,
                                                   pallas_status)
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    use_pallas, pallas_reason = pallas_status()  # the framework's kernel gate
    B, H, S, D = (4, 8, 4096, 128) if on_tpu else (2, 2, 512, 64)
    # methodology (dedup-proof, single-dispatch lax.map) is shared with
    # tools/flash_tune.py via tools/attn_timing so the tuner's block-size
    # choice and this reported number can never drift apart
    sys.path.insert(0, _HERE)
    from tools import attn_timing
    n_iter = 16 if on_tpu else 2
    dt_ = jnp.bfloat16 if on_tpu else jnp.float32
    qs, k, v = attn_timing.make_inputs(B, H, S, D, n_iter, dt_)
    bq, bk = (1024, 512) if on_tpu else (256, 256)
    # why the gate is open/closed is part of the record: "false" alone
    # can't distinguish a missing chip from a broken Pallas toolchain
    out = {"flash_attn_pallas": bool(use_pallas),
           "flash_attn_pallas_reason": pallas_reason}
    # per-mesh-axis roofline at the measured shape: what each dp/tp
    # shard of the mesh kernel tier (parallel/mesh_kernels.py) must move
    # under the dryrun's reference dp=4 x tp=2 factorization — analytic,
    # so it lands in the record even when the chip is absent
    from mxnet_tpu.parallel.mesh_kernels import flash_mesh_roofline

    class _RefMesh:  # shape-only stand-in for the dryrun's 8-way mesh
        shape = {"dp": 4, "tp": 2}
    out["flash_mesh_roofline"] = flash_mesh_roofline(
        (B, H, S, D), _RefMesh(), itemsize=2 if on_tpu else 4,
        causal=True)
    if not use_pallas:
        # jnp blockwise fallback: 'variant' has no effect there, so no
        # per-family labels that could read as Pallas evidence
        tflops, _ = attn_timing.timed_map_tflops(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            block_q=bq, block_k=bk,
                                            use_pallas=False),
            qs, k, v, attn_timing.causal_flops(B, H, S, D, n_iter))
        out["flash_attn_tflops"] = round(tflops, 2)
        out["flash_measured_vs_ideal"] = None  # no roofline off-chip
        return out
    best = None
    # both Pallas kernel families (stream: whole-KV VMEM + fori_loop;
    # grid: KV as an arbitrary grid dim) — report each and the winner.
    # Block sizes: tools/flash_tune.py pins per-family sweep winners into
    # flash_tune_results.json; fall back to sane starting points when no
    # pin exists. A failing family must not discard the other's number.
    family_blocks = {"stream": (bq, bk), "grid": (512, 512)}
    try:
        with open(os.path.join(_HERE, "flash_tune_results.json")) as f:
            for vname, row in (json.load(f).get("best_by_variant")
                               or {}).items():
                if vname in family_blocks:
                    family_blocks[vname] = (row["block_q"], row["block_k"])
                    out["flash_blocks_%s" % vname] = "pinned %dx%d" % (
                        row["block_q"], row["block_k"])
    except (OSError, ValueError, KeyError, TypeError):
        pass
    for variant, (vbq, vbk) in family_blocks.items():
        try:
            tflops, _ = attn_timing.timed_map_tflops(
                lambda q, k, v, fv=variant, a=vbq, b=vbk: flash_attention(
                    q, k, v, causal=True, block_q=a, block_k=b,
                    use_pallas=True, variant=fv),
                qs, k, v, attn_timing.causal_flops(B, H, S, D, n_iter))
        except Exception as e:
            out["flash_attn_%s_error" % variant] = "%s: %s" % (
                type(e).__name__, str(e)[:160])
            continue
        out["flash_attn_tflops_%s" % variant] = round(tflops, 2)
        if best is None or tflops > best[1]:
            best = (variant, tflops)
    if best is not None:
        out["flash_attn_tflops"] = round(best[1], 2)
        out["flash_attn_variant"] = best[0]
        # roofline gate: achieved TFLOP/s vs this chip's ceiling at the
        # kernel's arithmetic intensity (same flops/ideal-bytes figures
        # the cost phase emits as flash_fwd_gflops/flash_ideal_bytes_mb)
        flops1 = attn_timing.causal_flops(B, H, S, D)
        ideal_bytes = attn_timing.ideal_hbm_bytes(B, H, S, D)
        ideal = _tpu_roofline_tflops(
            getattr(jax.devices()[0], "device_kind", ""), flops1,
            ideal_bytes)
        if ideal:
            out["flash_measured_vs_ideal"] = round(best[1] / ideal, 3)
            from mxnet_tpu import profiler as _prof
            _prof.record_kernel_roofline("flash_attention_fwd", best[1],
                                         ideal, unit="tflops")
            out["kernel_roofline"] = _prof.kernel_counters()
        else:
            out["flash_measured_vs_ideal"] = None
    return out


def _phase_flash_parity():
    """On-chip, NON-interpret fwd+bwd parity of both Pallas kernel
    families vs the jnp blockwise path, at the PINNED production block
    sizes (tools/flash_tune.run_parity — one shared dtype/tolerance
    table). CI runs these kernels interpret-mode only (no TPU), so
    kernel-side regressions (VMEM overflow, Mosaic layout errors) would
    otherwise surface first at bench time.

    RAISES when the platform is not TPU: an empty rc-0 result would read
    as a validation that never ran — a failed phase is the truthful
    outcome."""
    import jax
    from mxnet_tpu.kernels.flash_attention import (flash_attention,
                                                   blockwise_attention,
                                                   default_use_pallas)
    if not default_use_pallas():
        raise RuntimeError("flash_parity: no TPU backend (pallas gate "
                           "off) — nothing to validate")
    import jax.numpy as jnp
    sys.path.insert(0, _HERE)
    from tools.flash_tune import run_parity, load_pinned_blocks
    return run_parity(
        jax, jnp, flash_attention, blockwise_attention,
        pinned_blocks=load_pinned_blocks(
            os.path.join(_HERE, "flash_tune_results.json")))


def _phase_infer_int8():
    """Post-training int8 inference: quantize_model rewrites ResNet-50
    conv/FC into `_contrib_quantized_*` ops executing on genuine int8
    operands (ops/quantization.py strategy table: int32 MXU accumulation
    on TPU, exact chunked-f32 accumulation for XLA:CPU convs, int32-
    accumulating int8 dot for FC everywhere).

    `int8_mode` is read off the TRACED JAXPR of the program this phase
    actually times (contrib.quantization.inspect_int8_program), never
    inferred from the backend name. The fp32 twin of the SAME model/shape
    is measured in the SAME child, so `int8_speedup_vs_f32` is a clean
    like-for-like ratio; `int8_measured_vs_ideal` gates it against the
    roofline expectation (2x on the MXU's s8 path, 1x for the f32-rate
    CPU accumulator — docs/faq/perf.md)."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.contrib import quantization as Q
    from mxnet_tpu.models import resnet
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    batch, n_iter = 32, (30 if on_tpu else 3)
    side = 224 if on_tpu else 64
    sym = resnet.get_symbol(num_classes=1000, num_layers=50 if on_tpu else 18,
                            image_shape="3,%d,%d" % (side, side))
    rng = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(batch, 3, side, side), softmax_label=(batch,))
    args = {}
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name not in ("data", "softmax_label"):
            args[name] = mx.nd.array(
                rng.normal(0, 0.01, shape).astype(np.float32))
    aux = {n: mx.nd.array(np.ones(s, np.float32) if "var" in n
                          else np.zeros(s, np.float32))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    calib = rng.uniform(-1, 1, (batch * 2, 3, side, side)).astype(np.float32)
    it = mx.io.NDArrayIter(calib, None, batch_size=batch)
    qsym, qargs, qaux, _ = Q.quantize_model(
        sym, args, aux, calib_mode="naive", calib_data=it,
        ctx=mx.tpu(0))  # calibrate on the device being benchmarked

    def bind(s, a, x):
        ba = dict(a)
        ba["data"] = mx.nd.zeros((batch, 3, side, side))
        ba["softmax_label"] = mx.nd.zeros((batch,))
        return s.bind(mx.tpu(0), ba, grad_req="null", aux_states=x)

    qexe = bind(qsym, qargs, qaux)
    fexe = bind(sym, args, aux)
    int8_ips = _timed_score_loop(qexe, batch, side, n_iter)
    f32_ips = _timed_score_loop(fexe, batch, side, n_iter)

    # ground truth: what do the timed program's contractions execute?
    arg_sds = {n: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
               for n, v in qexe.arg_dict.items()}
    aux_sds = {n: jax.ShapeDtypeStruct(tuple(v.shape), v.dtype)
               for n, v in qexe.aux_dict.items()}
    jaxpr = jax.make_jaxpr(
        lambda a, x: qexe._run_graph(a, x, jax.random.PRNGKey(0), False))(
        arg_sds, aux_sds)
    stats = Q.inspect_int8_program(jaxpr)

    speedup = round(int8_ips / f32_ips, 3) if f32_ips else None
    # roofline expectation for the int8 program vs its fp32 twin: the MXU
    # s8xs8->s32 path doubles the fp peak; the exact CPU accumulator runs
    # at f32 rate (ideal = parity). docs/faq/perf.md "Roofline counters".
    ideal_speedup = 2.0 if on_tpu else 1.0
    from mxnet_tpu import profiler as _prof
    out = {"int8_infer_img_per_sec": int8_ips,
           "int8_fp32_img_per_sec": f32_ips,
           "int8_speedup_vs_f32": speedup,
           "int8_measured_vs_ideal": (round(speedup / ideal_speedup, 3)
                                      if speedup is not None else None),
           "int8_mode": stats["mode"],
           "int8_contractions": {k: v for k, v in stats.items()
                                 if k != "mode"}}
    if speedup is not None:
        _prof.record_kernel_roofline("int8_infer", speedup, ideal_speedup,
                                     unit="speedup_vs_f32")
        # phases run in a child: the JSON line is the only surviving
        # channel, so the profiler snapshot rides the phase result
        out["kernel_roofline"] = _prof.kernel_counters()
    return out


def _phase_cost():
    """Hardware-independent analytic cost invariants (VERDICT r4 #9).

    Lowers the fused ResNet-50 train step (fp32 and bf16-compute) and the
    inference graph to HLO and records XLA's analytic FLOPs / bytes
    (`jit(...).lower(...).cost_analysis()`), plus the closed-form flash-
    attention FLOP count at the production benchmark shape. These give
    every round a chip-independent fingerprint: a graph-level regression
    (extra transposes, a lost fusion, an accidental fp32 upcast) moves
    `step_gflops`/`step_bytes` with no hardware needed, and each figure
    converts to MFU the moment a wall-clock measurement lands."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import resnet
    from mxnet_tpu.parallel.mesh import data_parallel_mesh
    from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep

    batch = 32
    out = {}

    def _analyze(lowered):
        ca = lowered.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
        return round(flops / 1e9, 2), round(nbytes / 1e6, 2)

    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224")
    for tag, dt_ in (("", None), ("_bf16", "bfloat16")):
        mesh = data_parallel_mesh(jax.devices()[:1])
        step = DataParallelTrainStep(sym, mesh, lr=0.05, momentum=0.9,
                                     data_names=("data",),
                                     label_names=("softmax_label",),
                                     compute_dtype=dt_)
        step.init({"data": (batch, 3, 224, 224), "softmax_label": (batch,)})
        # lower from shapes only: no batch materialization (data and label
        # ride as separate args in the fused step signature)
        abstract_data = {
            "data": jax.ShapeDtypeStruct((batch, 3, 224, 224), jnp.float32)}
        abstract_label = {
            "softmax_label": jax.ShapeDtypeStruct((batch,), jnp.float32)}
        lowered = step._step.lowered(step.params, step.opt_state, step.aux,
                                     abstract_data, abstract_label,
                                     jax.random.PRNGKey(0),
                                     np.float32(0.05))
        gflops, mbytes = _analyze(lowered)
        out["step%s_gflops" % tag] = gflops
        out["step%s_bytes_mb" % tag] = mbytes

    # inference graph (the headline phase's program, batch 32 fp32)
    import mxnet_tpu as mx
    from mxnet_tpu.executor import Executor
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(batch, 3, 224, 224), softmax_label=(batch,))
    args = {n: mx.nd.zeros(s)
            for n, s in zip(sym.list_arguments(), arg_shapes)}
    aux = {n: mx.nd.zeros(s)
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    exe = Executor(sym, mx.cpu(), args, {}, "null", aux)
    arg_sds = {n: jax.ShapeDtypeStruct(s, jnp.float32)
               for n, s in zip(sym.list_arguments(), arg_shapes)}
    aux_sds = {n: jax.ShapeDtypeStruct(s, jnp.float32)
               for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}

    def fwd(a, x):
        outs, _ = exe._run_graph(a, x, jax.random.PRNGKey(0), False)
        return outs[0]

    gflops, mbytes = _analyze(jax.jit(fwd).lower(arg_sds, aux_sds))
    out["infer_gflops"] = gflops
    out["infer_bytes_mb"] = mbytes

    # flash attention, closed form at the production benchmark shape
    # (B=4 H=8 S=4096 D=128 causal): FLOPs are kernel-family-independent;
    # ideal HBM traffic is Q+K+V+O in bf16
    sys.path.insert(0, _HERE)
    from tools.attn_timing import causal_flops, ideal_hbm_bytes
    B, H, S, D = 4, 8, 4096, 128
    out["flash_fwd_gflops"] = round(causal_flops(B, H, S, D) / 1e9, 2)
    out["flash_ideal_bytes_mb"] = round(ideal_hbm_bytes(B, H, S, D) / 1e6, 2)

    # fused optimizer-update roofline (kernels/opt_update.py): bytes of
    # the UPDATE-ONLY program vs the must-move floor. The update is pure
    # memory traffic, so bytes ARE the gate. Three figures:
    #   optupdate_bytes_mb        tree-map route, POST-FUSION (compiled)
    #                             cost analysis — what XLA actually moves
    #   optupdate_fused_bytes_mb  fused route as it runs on THIS backend
    #                             (kernel tier on TPU, lax tier off it)
    #   optupdate_kernel_bytes_mb the Pallas tier's DMA schedule (grid x
    #                             BlockSpec — exact on any host)
    from mxnet_tpu.kernels.opt_update import (fused_update_step,
                                              fused_update_available,
                                              optupdate_ideal_bytes,
                                              optupdate_kernel_bytes)
    from mxnet_tpu.parallel.optim_update import apply_update, init_opt_state
    params = {n: jnp.zeros(v.shape, jnp.float32)
              for n, v in step.params.items()}
    opt_state = init_opt_state("sgd", params, momentum=0.9)
    hp = {"lr": 0.05, "momentum": 0.9}
    rescale = 1.0 / batch

    def treemap_route(p, st, g, lr):
        g = {n: v * rescale for n, v in g.items()}
        g = {n: v + 1e-4 * p[n] for n, v in g.items()}
        return apply_update("sgd", dict(hp, lr=lr), p, st, g)

    def fused_route(p, st, g, lr):
        return fused_update_step("sgd", dict(hp, lr=lr), p, st, g,
                                 rescale=rescale, wd=1e-4)

    def _analyze_compiled(lowered):
        """Post-optimization bytes: the elementwise update chain fuses, so
        pre-fusion analysis would overcount every intermediate."""
        ca = lowered.compile().cost_analysis()
        return round(float(ca.get("bytes accessed", 0.0)) / 1e6, 2)

    sds = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
        (params, opt_state, params, np.float32(0.05)))
    for tag, route in (("optupdate", treemap_route),
                       ("optupdate_fused", fused_route)):
        out["%s_bytes_mb" % tag] = _analyze_compiled(
            jax.jit(route).lower(*sds))
    kernel_mb = round(
        optupdate_kernel_bytes("sgd", params, opt_state) / 1e6, 2)
    out["optupdate_kernel_bytes_mb"] = kernel_mb
    ideal_mb = round(optupdate_ideal_bytes("sgd", params, opt_state) / 1e6, 2)
    out["optupdate_ideal_bytes_mb"] = ideal_mb
    if ideal_mb:
        from mxnet_tpu import profiler as _prof
        for tag in ("optupdate", "optupdate_fused", "optupdate_kernel"):
            out["%s_measured_vs_ideal" % tag] = round(
                out["%s_bytes_mb" % tag] / ideal_mb, 3)
        # gate on the tier the flag actually engages on this backend
        gated = (kernel_mb if fused_update_available()
                 else out["optupdate_fused_bytes_mb"])
        _prof.record_kernel_roofline("opt_update", gated, ideal_mb,
                                     unit="bytes_mb")
        out["kernel_roofline"] = _prof.kernel_counters()

    # per-mesh-axis roofline for BOTH kernels (parallel/mesh_kernels.py)
    # at the multichip dryrun's reference dp=4 x tp=2 factorization of 8
    # devices. The roofline helpers only read `mesh.shape` as a mapping,
    # so a shape-only stand-in keeps this analytic phase device-free —
    # the same figures the dryrun banks from a live mesh.
    from mxnet_tpu.parallel.mesh_kernels import (flash_mesh_roofline,
                                                 optupdate_mesh_roofline)

    class _RefMesh:  # shape-only stand-in for get_mesh(dp=4, tp=2)
        shape = {"dp": 4, "tp": 2}
    out["flash_mesh_roofline"] = flash_mesh_roofline(
        (B, H, S, D), _RefMesh(), itemsize=2, causal=True)
    out["optupdate_mesh_roofline"] = optupdate_mesh_roofline(
        "sgd", params, _RefMesh(), opt_state=opt_state)
    return out


def _phase_serving():
    """Mixed-trace serving throughput through the serving subsystem
    (mxnet_tpu/serving/): individual requests with batch sizes 1..32 are
    queued async, the dynamic micro-batcher coalesces them into full
    buckets, and every dispatch hits a pre-compiled (warmup) XLA program
    with donated input buffers on TPU. The honest yardstick is measured in
    the SAME child: a plain pre-staged batch-32 executor loop over the
    same number of images (`serving_plain_b32_img_per_sec`) — bucketing +
    padding + coalescing must sustain >= it (`serving_vs_plain`)."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    from mxnet_tpu.serving import InferenceEngine
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    side = 224 if on_tpu else 64
    layers = 50 if on_tpu else 18
    # CPU fallback: a single bucket keeps the phase deterministic (every
    # coalesced group pads to 32 — no surprise mid-trace compiles on the
    # 1-core host); TPU warms the full production bucket ladder
    buckets = (1, 4, 8, 16, 32) if on_tpu else (32,)
    sym = resnet.get_symbol(num_classes=1000, num_layers=layers,
                            image_shape="3,%d,%d" % (side, side))
    rng = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(32, 3, side, side), softmax_label=(32,))
    args = {n: mx.nd.array(rng.normal(0, 0.01, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    aux = {n: mx.nd.array(np.ones(s, np.float32) if "var" in n
                          else np.zeros(s, np.float32))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    # CPU fallback: nproc=1, so the threaded worker only adds context-
    # switch thrash against the single-threaded plain loop — drive the
    # same coalesce/pad/dispatch path on the calling thread via flush()
    eng = InferenceEngine(sym, args, aux, ctx=mx.tpu(0), buckets=buckets,
                          max_batch=32, max_delay_ms=5.0,
                          async_worker=on_tpu)
    tic = time.time()
    eng.warmup({"data": (32, 3, side, side)})
    warmup_s = time.time() - tic

    # mixed 1-32 request trace (deterministic shuffle of the size ladder)
    trng = np.random.RandomState(7)
    sizes = [1, 2, 4, 8, 16, 32]
    trace = []
    for _ in range(20 if on_tpu else 2):
        trace.extend(int(s) for s in trng.permutation(sizes))
    total_imgs = sum(trace)
    pool = rng.uniform(-1, 1, (32, 3, side, side)).astype(np.float32)

    def serve_once():
        tic = time.time()
        futs = [eng.predict_async({"data": pool[:n]}) for n in trace]
        if not on_tpu:
            eng.flush()  # single-threaded drain (async_worker=False above)
        outs = [f.result_wait(PHASE_BUDGET_S["serving"]) for f in futs]
        # futures resolve at dispatch (async device queue); the clock
        # stops when every request's rows are actually computed — the
        # same wait-at-end protocol as _timed_score_loop
        jax.block_until_ready([o for out in outs for o in out])
        return time.time() - tic

    # same-child plain executor baseline, batch 32, same image count
    exe = sym.simple_bind(mx.tpu(0), grad_req="null",
                          data=(32, 3, side, side), softmax_label=(32,))
    for name, arr in args.items():
        arr.copyto(exe.arg_dict[name])
    for name, arr in aux.items():
        arr.copyto(exe.aux_dict[name])
    n_iter = max(1, total_imgs // 32)

    serve_once()  # warm the worker thread + any unwarmed remainder bucket
    # a shared CPU host's slow states last seconds-to-tens-of-seconds,
    # so on the CPU the comparison interleaves MANY SHORT
    # serve/plain pairs (alternating order so linear drift cancels) and
    # takes the median of per-pair ratios
    serve_rates, plain_rates, pair_ratios = [], [], []
    for i in range(5 if not on_tpu else 1):
        if i % 2 == 0:
            s = total_imgs / serve_once()
            p = _timed_score_loop(exe, 32, side, n_iter)
        else:
            p = _timed_score_loop(exe, 32, side, n_iter)
            s = total_imgs / serve_once()
        serve_rates.append(s)
        plain_rates.append(p)
        pair_ratios.append(s / p)
    med = lambda v: sorted(v)[len(v) // 2]  # noqa: E731
    st = eng.stats()
    eng.stop()
    out = {"serving_req_per_sec": round(
               med(serve_rates) * len(trace) / total_imgs, 2),
           "serving_img_per_sec": round(med(serve_rates), 2),
           "serving_plain_b32_img_per_sec": round(med(plain_rates), 2),
           # median of PER-PAIR ratios: each pair ran under the same host
           # state, so drift cancels. Structurally this converges to ~1.0
           # (the serving machinery costs <0.1% of a ResNet batch) —
           # values off 1.0 beyond a few % are host noise
           "serving_vs_plain": round(med(pair_ratios), 3),
           "serving_warmup_s": round(warmup_s, 1),
           "serving_compiles": st["compiles"],
           "serving_batches": st["batches_run"],
           "serving_padded_rows": st["padded_rows"]}

    # the NAIVE mixed-trace baseline — what this traffic costs WITHOUT the
    # serving engine: each request forwards individually through the bound
    # executor, per-shape jit (the pre-serving predict path). Steady-state
    # (first pass pays the per-size compiles and is excluded), so the
    # ratio isolates coalescing + bucket reuse, not compile amortization.
    def naive_once():
        tic = time.time()
        for n in trace:
            exe.forward(is_train=False,
                        data=mx.nd.array(pool[:n].copy()))
        exe.outputs[0].wait_to_read()
        return total_imgs / (time.time() - tic)

    try:
        naive_once()  # compile every distinct request size
        naive = med([naive_once() for _ in range(3 if not on_tpu else 1)])
        out["serving_naive_trace_img_per_sec"] = round(naive, 2)
        out["serving_vs_naive"] = round(out["serving_img_per_sec"] / naive,
                                        3)
    except Exception as e:  # a failed baseline must not kill the phase
        out["serving_naive_error"] = "%s: %s" % (type(e).__name__,
                                                 str(e)[:120])
    return out


def _phase_serving_sla():
    """SLA goodput under overload (ISSUE 8): a bursty OPEN-LOOP trace —
    arrivals on a fixed schedule at 2x the engine's measured capacity,
    regardless of completions — against a deadline a few step times wide.
    The metric that matters at this layer is goodput-under-deadline, not
    raw req/s: without load shedding an overloaded queue grows without
    bound and EVERY request's latency collapses together; with the
    deadline-driven batcher, hopeless requests fast-fail (`shed_rate`)
    and the SERVED distribution's p99 stays inside the SLA. Reports
    `goodput_under_sla` (served-within-deadline / submitted), `shed_rate`,
    and client-side p50/p95/p99 of served requests, plus the per-model
    latency histograms from profiler.latency_counters()."""
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import ModelServer, DeadlineExceeded
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    # model sized so one bucket step lands in the tens-of-ms band on the
    # host: the phase measures the SERVING tier's scheduling, and a
    # millisecond-scale step makes the host's own scheduling noise (GIL
    # handoffs, container stalls — tens of ms on the CPU fallback) LARGER
    # than the step, so every latency percentile measures the host, not
    # the batcher. A step that dwarfs the noise also keeps the worker
    # inside XLA (GIL released) while the open-loop submitter sleeps
    # between bursts.
    hidden = 1024
    indim = 128
    bucket = 8
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="sla_fc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="sla_fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="sla_fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(bucket, indim))
    args = {n: mx.nd.array(rng.normal(0, 0.05, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    profiler.latency_counters(reset=True, prefix="serving.sla_model")
    srv = ModelServer()
    # shed_margin 2.5 on the decaying-MAX step estimate: a request
    # dispatched right at the feasibility edge must survive a service-
    # time SPIKE (GIL handoff, GC, scheduler), not the mean — budgeting
    # the tail is what keeps served p99 INSIDE the SLA on a noisy host
    # instead of pecking at the deadline from above
    srv.register("sla_model", sym, args, ctx=mx.tpu(0), buckets=(bucket,),
                 max_delay_ms=1.0, slack_factor=3.0, shed_margin=2.5,
                 warmup_shapes={"data": (bucket, indim)})
    eng = srv.engine("sla_model")

    # measured capacity from the REAL async serving path AT SATURATION
    # (worker thread, staging, coalescing — not the bare sync loop): time
    # the drain of a deadline-less burst. The drain also primes the
    # program cache's per-bucket EWMA under load — the shedder's signal.
    xb = rng.uniform(-1, 1, (bucket, indim)).astype(np.float32)
    x1 = xb[:1]
    for _ in range(bucket * 2):  # warm: worker thread + program path
        srv.predict_async("sla_model", {"data": x1}).result_wait(60.0)
    n_cal = bucket * 20
    tic = time.monotonic()
    cal = [srv.predict_async("sla_model", {"data": x1})
           for _ in range(n_cal)]
    for f in cal:
        f.result_wait(60.0)
    capacity_rps = n_cal / (time.monotonic() - tic)
    batch_s = bucket / capacity_rps  # saturated per-batch service time
    gap_s = max(batch_s / 2.0, 1.5e-3)  # floor: the submitter must sleep

    def open_loop(n_bursts, deadline_ms):
        fs = []
        start = time.monotonic()
        for b in range(n_bursts):
            target = start + b * gap_s
            now = time.monotonic()
            if target > now:
                time.sleep(target - now)
            for _ in range(bucket):
                fs.append(srv.predict_async("sla_model", {"data": x1},
                                            deadline_ms=deadline_ms))
        return fs, start

    # PILOT overload (deadline-less, ~0.4 s at the 2x schedule): sustained
    # submit/serve thread interleaving is what produces this host's
    # service-time SPIKES (GIL handoffs on the 1-core fallback), and the
    # decaying-max tail estimate must learn that contended profile BEFORE
    # an SLA is set against it — an SLA below the host's own scheduling
    # tail is unservable by any batcher
    pilot, _ = open_loop(max(12, int(0.4 / gap_s)), None)
    for f in pilot:
        f.result_wait(60.0)
    step_s = eng.step_time(bucket) or batch_s
    tail_s = eng._cache.step_time_tail(bucket) or step_s
    # SLA floor: ~3x the host's worst scheduling stall, or a request
    # selected with honest slack still resolves late when a stall lands
    # on its batch and p99 pecks over the deadline from above. The 1-core
    # CPU fallback's measured stall tail is 30-70 ms (GIL handoffs +
    # container scheduler), hence 200 ms there; a real accelerator host
    # serves the tight 25 ms floor.
    sla_floor_ms = 25.0 if on_tpu else 200.0
    sla_ms = max(8.0 * batch_s * 1e3, 2.5 * 1.5 * tail_s * 1e3,
                 sla_floor_ms)
    base = eng.stats()                    # pilot counters, subtracted below
    profiler.latency_counters(reset=True, prefix="serving.sla_model")

    # measured trace: open-loop bursty arrivals at 2x capacity — bursts of
    # `bucket` back-to-back requests, burst starts spaced
    # bucket/(2*capacity) — long enough (>= 10 SLA windows, capped at
    # 2000 requests) that the backlog a 2x overload necessarily builds
    # crosses the deadline and shedding MUST engage (an open loop never
    # slows down to match completions)
    # requests carry an INTERNAL deadline 15% tighter than the external
    # SLA (SRE-style error budget): under saturation EDF serves everything
    # just-in-time, pinning the served distribution AT the shed edge — an
    # edge at 0.85x SLA puts p99 ~0.85x SLA with the remaining 15% as the
    # guard band for scheduling stalls the tail estimate hasn't seen
    duration_s = max(0.4, 10.0 * sla_ms / 1e3)
    n_bursts = max(12, min(2000 // bucket, int(duration_s / gap_s)))
    futs, t0 = open_loop(n_bursts, 0.85 * sla_ms)
    submit_wall_s = time.monotonic() - t0   # the offered-rate window ends
    submitted = len(futs)                   # here, not after the drain
    # steady-state window: the decaying-max tail estimate (the shedder's
    # spike budget) needs the first batches of the trace to LEARN this
    # host's spike profile, so SLO percentiles follow standard practice
    # and exclude the ramp; full-trace accounting and p99 are reported
    # alongside so nothing hides
    ramp = submitted // 4
    served, shed, errors, lat_all, lat_steady = 0, 0, 0, [], []
    for i, f in enumerate(futs):
        try:
            f.result_wait(PHASE_BUDGET_S["serving_sla"])
            served += 1
            ms = (f.t_done - f.t_submit) * 1e3
            lat_all.append(ms)
            if i >= ramp:
                lat_steady.append(ms)
        except DeadlineExceeded:
            shed += 1
        except Exception:
            errors += 1
    wall_s = time.monotonic() - t0
    lat_all.sort()
    lat_steady.sort()

    def pct(vals, q):
        return round(vals[min(int(q * len(vals)), len(vals) - 1)], 2) \
            if vals else None

    within = sum(1 for v in lat_all if v <= sla_ms)
    if not lat_steady:      # everything served landed in the ramp: judge
        lat_steady = lat_all  # on the full trace rather than report None
    st = eng.stats()
    out = {
        "sla_ms": round(sla_ms, 2),
        "sla_step_ms": round(step_s * 1e3, 3),
        "sla_capacity_rps": round(capacity_rps, 1),
        "sla_offered_rps": round(submitted / max(submit_wall_s, 1e-9), 1),
        "sla_submitted": submitted,
        "sla_served": served,
        "sla_shed": shed,
        "sla_errors": errors,
        "goodput_under_sla": round(within / float(submitted), 3),
        "shed_rate": round(shed / float(submitted), 3),
        "sla_p50_ms": pct(lat_steady, 0.50),
        "sla_p95_ms": pct(lat_steady, 0.95),
        "sla_p99_ms": pct(lat_steady, 0.99),
        "sla_p99_within_sla": bool(lat_steady)
        and pct(lat_steady, 0.99) <= sla_ms,
        "sla_p99_full_trace_ms": pct(lat_all, 0.99),
        "sla_overload_factor": round(
            (submitted / max(submit_wall_s, 1e-9)) / capacity_rps, 2),
        "sla_accounting_exact": served + shed + errors == submitted,
        "sla_early_dispatches": st["early_dispatches"]
        - base["early_dispatches"],
        "sla_batches": st["batches_run"] - base["batches_run"],
        "sla_step_tail_ms": st["step_tail_ms"],
        "sla_latency_counters": profiler.latency_counters(
            prefix="serving.sla_model"),
    }
    srv.stop()
    return out


def _phase_io_train():
    """End-to-end input-pipeline + train throughput: synthetic JPEG .rec ->
    C++ ImageRecordIter (sharded read, threaded decode/augment, prefetch;
    src/io/image_record_iter.cc) -> Module.fit on the fused tpu_sync step.
    This is the judged `train_imagenet.py` path WITH its IO half, where the
    other train phases pre-stage device tensors. Also reports the pure
    pipeline drain rate. Reference anchor: iter_image_recordio_2.cc:50."""
    import tempfile
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import recordio
    from mxnet_tpu.models import resnet
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    side = 224 if on_tpu else 64
    n_img = 512 if on_tpu else 192
    batch = 32
    rng = np.random.RandomState(0)
    import atexit
    import shutil
    tmpdir = tempfile.mkdtemp()
    atexit.register(shutil.rmtree, tmpdir, True)  # child exits -> cleanup
    path = os.path.join(tmpdir, "synthetic.rec")
    rec = recordio.MXRecordIO(path, "w")
    # photo-like synthetic frames (smooth content + mild texture), not raw
    # noise: noise JPEGs are ~6x larger than real-photo JPEGs at this size
    # and overstate decode cost vs the ImageNet workload being modeled
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float32) / side
    for i in range(n_img):
        img = np.stack([128 + 90 * np.sin(2 * np.pi * (xx * 1.5 + i * .1)),
                        128 + 90 * np.cos(2 * np.pi * (yy * 1.2 + i * .07)),
                        128 + 60 * np.sin(2 * np.pi * (xx * yy + i * .05))],
                       axis=-1)
        img = np.clip(img + rng.normal(0, 6, img.shape), 0, 255)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0),
            img.astype(np.uint8), quality=90))
    rec.close()
    # uint8 over the host->device link (4x fewer bytes, no host-side
    # normalization pass on this single-core host); cast + per-channel
    # normalize are folded into the XLA graph below
    it = mx.io.ImageRecordIter(
        path_imgrec=path, data_shape=(3, side, side), batch_size=batch,
        shuffle=True, preprocess_threads=8, rand_mirror=True, dtype="uint8",
        mean_r=123.0, mean_g=117.0, mean_b=104.0, std_r=58.0, std_g=57.0,
        std_b=57.0)
    n = 0
    tic = time.time()
    for _ in it:  # pure pipeline drain: decode+augment+batch, no compute
        n += batch
    pipeline_ips = n / (time.time() - tic)
    it.reset()
    body = resnet.get_symbol(num_classes=1000,
                             num_layers=50 if on_tpu else 18,
                             image_shape="3,%d,%d" % (side, side))
    sym = it.normalize_prelude(body)
    mod = mx.mod.Module(sym, context=mx.tpu(0))
    step_times = []
    from mxnet_tpu import profiler as _prof
    _prof.pipeline_counters(reset=True)  # fresh overlap counters for fit
    mod.fit(it, num_epoch=3 if on_tpu else 2, kvstore="tpu_sync",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            initializer=mx.init.Xavier(rnd_type="gaussian", magnitude=2.0),
            batch_end_callback=lambda p: step_times.append(time.time()))
    assert mod._fused_step is not None  # must measure the fused path
    pc = _prof.pipeline_counters(reset=True)
    half = len(step_times) // 2  # steady state: drop compile + warmup half
    ips = batch * (len(step_times) - half) \
        / max(step_times[-1] - step_times[half - 1], 1e-9)
    return {"io_train_img_per_sec": round(ips, 2),
            "io_pipeline_img_per_sec": round(pipeline_ips, 2),
            # overlap efficiency of the pipeline (profiler pipeline
            # counters): hit = next batch was already device-staged when
            # the loop asked; stall = the loop waited on the stager;
            # readback_stall = bounded-dispatch blocking on step i-depth
            "io_overlap_extra": {
                "prefetch_hit": int(pc.get("prefetch_hit", 0)),
                "prefetch_stall": int(pc.get("prefetch_stall", 0)),
                "prefetch_stall_ms": round(pc.get("prefetch_stall_ms", 0.0), 2),
                "prefetch_stage_ms": round(pc.get("prefetch_stage_ms", 0.0), 2),
                "dispatch_ms": round(pc.get("dispatch_ms", 0.0), 2),
                "readback_stall_ms": round(pc.get("readback_stall_ms", 0.0), 2),
                "steps": int(pc.get("steps", 0))}}


# The front-door bench client: a REAL second OS process driving the TCP
# gateway closed-loop. Reports per-request client latency plus the
# server's per-request timing breakdown, so added wire cost is measured
# per request (client wall - server queue - server device), not inferred
# from separate runs.
_FRONTDOOR_CLIENT = r'''
import json, os, sys, time
sys.path.insert(0, %(root)r)
import numpy as np
from mxnet_tpu.serving import ServingClient
port, seed, n_req, rows = (int(sys.argv[1]), int(sys.argv[2]),
                           int(sys.argv[3]), int(sys.argv[4]))
# optional 5th arg: wire codec mode — "safe" (default) or "pickle"
# (the previous protocol), so the phase can bank the safe codec's
# per-request cost against the pickle baseline on the SAME gateway
mode = sys.argv[5] if len(sys.argv) > 5 else "safe"
cli = ServingClient("127.0.0.1", port, wire_mode=mode)
rng = np.random.RandomState(seed)
x = rng.uniform(-1, 1, (rows, %(indim)d)).astype(np.float32)
# warm the connection + program path outside the timed window
for _ in range(3):
    cli.predict({"data": x}, model="frontdoor", timeout=120.0)
lat, added = [], []
tic = time.monotonic()
for i in range(n_req):
    t0 = time.monotonic()
    f = cli.predict_async({"data": x}, model="frontdoor")
    f.result_wait(120.0)
    ms = (time.monotonic() - t0) * 1e3
    lat.append(ms)
    t = f.timings or {}
    added.append(ms - t.get("queue_ms", 0.0) - t.get("device_ms", 0.0))
wall = time.monotonic() - tic
lat.sort(); added.sort()
def pct(v, q):
    return v[min(int(q * len(v)), len(v) - 1)] if v else None
print(json.dumps({
    "n": n_req, "wall_s": wall,
    "lat_p50_ms": pct(lat, 0.5), "lat_p99_ms": pct(lat, 0.99),
    "added_p50_ms": pct(added, 0.5), "added_p99_ms": pct(added, 0.99)}))
cli.close()
'''


def _phase_frontdoor():
    """Cross-process serving gateway (ISSUE 11): N client OS processes
    drive the TCP front door against the in-process baseline. Reports
    `frontdoor_req_per_sec` (aggregate closed-loop across the socket)
    vs `frontdoor_inprocess_req_per_sec` (same trace, same process),
    the ADDED wire latency per request (client wall minus the server's
    own queue+device time, p50/p99 — serialization + TCP + demux), and
    goodput under a 2x open-loop overload ACROSS the socket with the
    served p99 decomposed into wire/queue/device from the trace-id
    latency histograms. A graceful drain closes the phase and its
    accounting must be exact."""
    import subprocess
    import numpy as np
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.serving import (ModelServer, ServingFrontDoor,
                                   ServingClient, DeadlineExceeded)
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    # same model shape logic as serving_sla: a step in the tens-of-ms
    # band so the serving/network tier is what gets measured, not host
    # scheduling noise
    hidden = 1024
    indim = 128
    bucket = 8
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="fdb_fc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name="fdb_fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fdb_fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    shapes, _, _ = sym.infer_shape(data=(bucket, indim))
    args = {n: mx.nd.array(rng.normal(0, 0.05, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    profiler.latency_counters(reset=True, prefix="serving.frontdoor.")
    srv = ModelServer()
    srv.register("frontdoor", sym, args, ctx=mx.tpu(0), buckets=(bucket,),
                 max_delay_ms=1.0, slack_factor=3.0, shed_margin=2.5,
                 warmup_shapes={"data": (bucket, indim)})
    fd = ServingFrontDoor(srv, port=0).start()
    xb = rng.uniform(-1, 1, (bucket, indim)).astype(np.float32)
    x1 = xb[:1]

    # --- in-process baseline: same closed-loop trace, no socket -------
    n_base = bucket * 12
    for _ in range(bucket):
        srv.predict_async("frontdoor", {"data": x1}).result_wait(120.0)
    tic = time.monotonic()
    for _ in range(n_base):
        srv.predict_async("frontdoor", {"data": x1}).result_wait(120.0)
    inproc_rps = n_base / (time.monotonic() - tic)

    # --- N client processes, closed loop over the socket --------------
    n_clients = 2
    n_req = bucket * 12
    script = _FRONTDOOR_CLIENT % {"root": _HERE, "indim": indim}

    def _client_pass(mode):
        tic = time.monotonic()
        procs = [subprocess.Popen(
            [sys.executable, "-c", script, str(fd.port), str(seed),
             str(n_req), "1", mode], stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))  # host-side client
            for seed in range(1, n_clients + 1)]
        reports = []
        for p in procs:
            out_s, _ = p.communicate(timeout=PHASE_BUDGET_S["frontdoor"])
            if p.returncode != 0:
                raise RuntimeError("frontdoor bench client failed: %s"
                                   % out_s[-500:])
            reports.append(json.loads(out_s.strip().splitlines()[-1]))
        return reports, time.monotonic() - tic

    reports, wall = _client_pass("safe")
    total_req = sum(r["n"] for r in reports)
    wire_rps = total_req / wall
    # same trace over the PREVIOUS protocol (pickle wire) on the same
    # gateway: the per-request p50/p99 added-wire-latency delta is the
    # safe codec's measured cost — banked, not guessed (ISSUE 13)
    reports_pickle, _ = _client_pass("pickle")
    codec_delta = {}
    for q in ("added_p50_ms", "added_p99_ms"):
        safe_q = max(r[q] for r in reports)
        pick_q = max(r[q] for r in reports_pickle)
        codec_delta["safe_" + q] = round(safe_q, 3)
        codec_delta["pickle_" + q] = round(pick_q, 3)
        codec_delta["delta_" + q] = round(safe_q - pick_q, 3)

    # --- codec micro-bench: encode+decode of one real request/reply ---
    from mxnet_tpu.serving import wire as _wire_mod
    spec_frame = ("predict", "c1-1",
                  {"model": "frontdoor", "version": None,
                   "arrays": {"data": xb}, "deadline_ms": 200.0,
                   "priority": 0, "trace": "bench-codec",
                   "t_send": time.time()})
    reply_frame = ("served", "c1-1",
                   [np.zeros((bucket, 10), np.float32)],
                   {"trace": "bench-codec", "wire_ms": 0.5,
                    "queue_ms": 2.0, "device_ms": 10.0, "total_ms": 12.5})
    codec_us = {}
    for codec_name in ("safe", "pickle"):
        enc_us, dec_us = [], []
        for frame in (spec_frame, reply_frame):
            payload = _wire_mod.encode_payload(frame, codec=codec_name)
            for _ in range(300):
                t0 = time.perf_counter_ns()
                _wire_mod.encode_payload(frame, codec=codec_name)
                t1 = time.perf_counter_ns()
                _wire_mod.decode_payload(payload)
                t2 = time.perf_counter_ns()
                enc_us.append((t1 - t0) / 1e3)
                dec_us.append((t2 - t1) / 1e3)
        enc_us.sort()
        dec_us.sort()
        codec_us[codec_name] = {
            "encode_p50_us": round(enc_us[len(enc_us) // 2], 2),
            "decode_p50_us": round(dec_us[len(dec_us) // 2], 2),
            "encode_p99_us": round(enc_us[int(0.99 * len(enc_us))], 2),
            "decode_p99_us": round(dec_us[int(0.99 * len(dec_us))], 2)}

    # --- 2x open-loop overload ACROSS the socket ----------------------
    cli = ServingClient("127.0.0.1", fd.port, pool_size=2)
    eng = srv.engine("frontdoor")
    # SATURATED capacity over the socket (async backlog drain — the
    # closed-loop wire_rps above is round-trip-bound, not a capacity):
    # the overload schedule and the SLA both key off this, exactly like
    # the in-process serving_sla phase
    n_cal = bucket * 16
    tic = time.monotonic()
    cal = [cli.predict_async({"data": x1}, model="frontdoor")
           for _ in range(n_cal)]
    for f in cal:
        f.result_wait(PHASE_BUDGET_S["frontdoor"])
    capacity_rps = n_cal / (time.monotonic() - tic)
    # the p99 decomposition below must describe the OVERLOAD window, not
    # a blend with the baseline/closed-loop/calibration traffic recorded
    # so far (same reason serving_sla uses a steady-state window)
    profiler.latency_counters(reset=True, prefix="serving.frontdoor.")
    tail_s = eng._cache.step_time_tail(bucket) or 0.01
    sla_floor_ms = 25.0 if on_tpu else 200.0
    sla_ms = max(8.0 * bucket / max(capacity_rps, 1e-6) * 1e3,
                 2.5 * 1.5 * tail_s * 1e3, sla_floor_ms)
    gap_s = max(bucket / max(2.0 * capacity_rps, 1e-6), 1.5e-3)
    duration_s = max(0.4, 8.0 * sla_ms / 1e3)
    n_bursts = max(12, min(1600 // bucket, int(duration_s / gap_s)))
    futs = []
    start = time.monotonic()
    for b in range(n_bursts):
        target = start + b * gap_s
        now = time.monotonic()
        if target > now:
            time.sleep(target - now)
        for _ in range(bucket):
            futs.append(cli.predict_async({"data": x1}, model="frontdoor",
                                          deadline_ms=0.85 * sla_ms))
    submit_wall_s = time.monotonic() - start
    served = shed = errors = 0
    lat = []
    for f in futs:
        try:
            f.result_wait(PHASE_BUDGET_S["frontdoor"])
            served += 1
            t = f.timings or {}
            if "total_ms" in t:
                lat.append(t["total_ms"])
        except DeadlineExceeded:
            shed += 1
        except Exception:
            errors += 1
    submitted = len(futs)
    lat.sort()

    def pct(vals, q):
        return round(vals[min(int(q * len(vals)), len(vals) - 1)], 2) \
            if vals else None

    within = sum(1 for v in lat if v <= sla_ms)
    hist = profiler.latency_counters(prefix="serving.frontdoor.")
    decomp = {leg: hist.get("serving.frontdoor.%s" % leg, {}).get("p99_ms")
              for leg in ("wire", "queue", "device", "total")}
    cli.close()
    drain_clean = fd.drain(timeout=60.0)
    st = fd.stats()
    srv.stop()
    return {
        "frontdoor_req_per_sec": round(wire_rps, 1),
        "frontdoor_inprocess_req_per_sec": round(inproc_rps, 1),
        "frontdoor_vs_inprocess": round(wire_rps / inproc_rps, 3)
        if inproc_rps else None,
        "frontdoor_clients": n_clients,
        "frontdoor_wire_added_p50_ms": round(max(
            r["added_p50_ms"] for r in reports), 3),
        "frontdoor_wire_added_p99_ms": round(max(
            r["added_p99_ms"] for r in reports), 3),
        "frontdoor_client_p50_ms": round(max(
            r["lat_p50_ms"] for r in reports), 3),
        "frontdoor_codec_wire_ms": codec_delta,
        "frontdoor_codec_us": codec_us,
        "frontdoor_capacity_rps": round(capacity_rps, 1),
        "frontdoor_sla_ms": round(sla_ms, 2),
        "frontdoor_overload_factor": round(
            (submitted / max(submit_wall_s, 1e-9))
            / max(capacity_rps, 1e-9), 2),
        "frontdoor_submitted": submitted,
        "frontdoor_served": served,
        "frontdoor_shed": shed,
        "frontdoor_errors": errors,
        "frontdoor_goodput_under_sla": round(within / float(submitted), 3),
        "frontdoor_shed_rate": round(shed / float(submitted), 3),
        "frontdoor_served_p99_ms": pct(lat, 0.99),
        "frontdoor_p99_decomposition_ms": decomp,
        "frontdoor_accounting_exact":
            served + shed + errors == submitted
            and st["submitted"] == st["served"] + st["shed"] + st["failed"],
        "frontdoor_drain_clean": bool(drain_clean),
        "frontdoor_orphaned": st["orphaned"],
    }


def _phase_fleet():
    """Cross-host serving fleet (ISSUE 12): the numbers behind the
    robustness claims. (a) Worker SIGKILL under open-loop load across
    two REAL worker processes: `fleet_recovery_ms` (kill -> first
    rerouted request resolving served), `fleet_goodput_dip` (worst
    100ms-window served rate over the pre-kill average) and
    `fleet_dip_duration_ms` (how long windows stayed below 90% of it),
    with exact accounting. (b) The autoscaler detects the dead worker
    via the health signal and restores capacity through the local
    process launcher: `fleet_autoscale_restore_ms`. (c) Hedged vs
    unhedged p99 under an injected 120ms straggler replica."""
    import signal as _signal
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving import (ModelServer, FleetPool, Autoscaler,
                                   LocalProcessLauncher, DeadlineExceeded)
    # the worker bootstrap AND the gateway's matching net/params come
    # from the shared fixture (same seed/names — the bit-identity check
    # below is cross-process, not cross-backend)
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    import fleet_worker_fixture as _fx

    rng = np.random.RandomState(0)
    sym = _fx.net()
    args = _fx.params(sym)
    out = {}

    gw = pool = launcher = asc = None
    try:
        # CPU-pinned on purpose: this phase measures fleet CONTROL-PLANE
        # dynamics (failure detection, reroute, autoscale, hedging) —
        # backend-agnostic by design, and a TPU gateway over CPU workers
        # would turn the bit-identity check into a cross-backend float
        # comparison
        gw = ModelServer(dispatch_retries=3)
        model = _fx.MODEL
        gw.register(model, sym, args, ctx=mx.cpu(), buckets=(1, 4),
                    max_delay_ms=0.5, warmup_shapes={"data": (4, 6)})
        pool = FleetPool(gw, port=0, heartbeat_s=0.25,
                         connect_deadline_s=1.0).start()
        env = {"PYTHONPATH": os.path.join(_HERE, "tools") + os.pathsep
               + _HERE + os.pathsep + os.environ.get("PYTHONPATH", "")}
        launcher = LocalProcessLauncher(
            "127.0.0.1:%d" % pool.port, "fleet_worker_fixture:build",
            env=env)
        launcher.launch()
        launcher.launch()
        deadline = time.monotonic() + 120.0
        while pool.stats()["workers_alive"] < 2:
            if time.monotonic() > deadline:
                raise RuntimeError("fleet bench workers never joined: %s"
                                   % pool.stats())
            time.sleep(0.1)
        x1 = rng.normal(0, 1, (1, 6)).astype(np.float32)
        want = np.asarray(gw.predict(model, {"data": x1})[0])
        # bit-identity THROUGH a remote worker, explicitly (the open-loop
        # trace below routes least-loaded, which favors the local
        # replica for its first requests)
        handle = next(iter(pool._workers.values()))
        remote_rep = next(iter(handle.replicas.values()))[0]
        remote_out = np.asarray(remote_rep.engine.predict_async(
            {"data": x1}).result_wait(60.0)[0])
        out["fleet_bit_identical"] = bool(
            np.array_equal(remote_out, want))

        # -- (a) SIGKILL one worker under open-loop load ---------------
        n_req, kill_at = 500, 200
        gap_s = 0.002
        futs, windows = [], {}
        t_kill = None
        t0 = time.monotonic()
        victim = launcher.alive()[0]
        for i in range(n_req):
            if i == kill_at:
                victim.send_signal(_signal.SIGKILL)
                t_kill = time.monotonic()
            futs.append((time.monotonic(),
                         gw.predict_async(model, {"data": x1},
                                          deadline_ms=8000.0)))
            time.sleep(gap_s)
        served = shed = failed = retried = 0
        t_recover = None
        for t_sub, f in futs:
            try:
                f.result_wait(60.0)
                served += 1
                win = int((f.t_done - t0) / 0.1)
                windows[win] = windows.get(win, 0) + 1
                if f.attempts > 1:
                    retried += 1
                    if t_recover is None or f.t_done < t_recover:
                        t_recover = f.t_done
            except DeadlineExceeded:
                shed += 1
            except Exception:
                failed += 1
        kill_win = int((t_kill - t0) / 0.1)
        pre = [windows.get(w, 0) for w in range(1, kill_win)]
        pre_avg = (sum(pre) / float(len(pre))) if pre else 0.0
        # exclude the final window: it is truncated by the trace simply
        # draining (completions stop mid-window), and its low count
        # would masquerade as a kill-induced dip — same reason `pre`
        # drops the ramp window 0
        post = {w: windows.get(w, 0)
                for w in range(kill_win, max(windows))} \
            if windows else {}
        dip = min(post.values()) / pre_avg if post and pre_avg else None
        below = [w for w, v in post.items() if pre_avg and
                 v < 0.9 * pre_avg]
        dip_dur_ms = ((max(below) - min(below) + 1) * 100.0) \
            if below else 0.0
        c = gw.stats()[model]["counters"]
        out["fleet_submitted"] = n_req
        out["fleet_served"] = served
        out["fleet_shed"] = shed
        out["fleet_failed"] = failed
        out["fleet_rerouted"] = retried
        out["fleet_accounting_exact"] = (
            served + shed + failed == n_req
            and c["submitted"] == c["served"] + c["shed"] + c["failed"])
        if t_recover is not None and t_kill is not None:
            out["fleet_recovery_ms"] = round((t_recover - t_kill) * 1e3,
                                             1)
        out["fleet_goodput_dip"] = round(dip, 3) if dip is not None \
            else None
        out["fleet_dip_duration_ms"] = round(dip_dur_ms, 1)

        # -- (b) autoscaler restores the dead worker's capacity --------
        asc = Autoscaler(pool.health, launcher, min_workers=2,
                         max_workers=3, interval_s=0.3, hysteresis=2,
                         cooldown_s=2.0)
        t_asc = time.monotonic()
        asc.start()
        restore_deadline = time.monotonic() + 120.0
        restored = False
        while time.monotonic() < restore_deadline:
            if pool.stats()["workers_alive"] >= 2:
                restored = True
                break
            time.sleep(0.1)
        out["fleet_autoscale_restored"] = restored
        if restored:
            out["fleet_autoscale_restore_ms"] = round(
                (time.monotonic() - t_asc) * 1e3, 1)
        out["fleet_autoscale_actions"] = list(asc.stats.items())
        asc.stop()
        pool.stop()
        gw.stop()
        launcher.stop_all()
        asc = pool = gw = launcher = None

        # -- (c) hedged vs unhedged p99 under a straggler replica ------
        def _tail_run(hedge_ms):
            from mxnet_tpu import profiler as _prof
            faults.reset()
            # the device histogram is process-global: the UNHEDGED run's
            # 120ms stragglers would otherwise inflate the hedged run's
            # auto-derived delay past the straggler itself (no hedge
            # would ever fire) — each run derives from its own samples
            _prof.latency_counters(reset=True, prefix="serving.flb")
            srv = ModelServer(hedge_ms=hedge_ms)
            srv.register("flb", sym, args, ctx=mx.tpu(0), replicas=2,
                         buckets=(1, 4), max_delay_ms=0.5,
                         warmup_shapes={"data": (4, 6)})
            for _ in range(8):   # teach the device histogram
                srv.predict_async("flb", {"data": x1}).result_wait(60.0)
            faults.configure("serving.dispatch:replica=0:mode=async:"
                             "prob=0.25:seed=3:delay=120")
            lats = []
            for _ in range(150):
                tic = time.monotonic()
                srv.predict_async("flb", {"data": x1},
                                  deadline_ms=8000.0).result_wait(60.0)
                lats.append((time.monotonic() - tic) * 1e3)
            faults.reset()
            hedges = srv.stats()["flb"]["counters"]["hedges"]
            srv.stop()
            lats.sort()
            return lats[int(0.99 * len(lats))], hedges
        # hedge_ms=False forces the baseline UNHEDGED even when the
        # operator exported MXNET_SERVING_HEDGE_MS (None would defer to
        # it and silently hedge both runs)
        p99_plain, _ = _tail_run(False)
        p99_hedged, n_hedges = _tail_run(0.0)   # auto-derived delay
        out["fleet_unhedged_p99_ms"] = round(p99_plain, 1)
        out["fleet_hedged_p99_ms"] = round(p99_hedged, 1)
        out["fleet_hedges_fired"] = n_hedges
        out["fleet_hedge_p99_speedup"] = round(p99_plain / p99_hedged,
                                               2) if p99_hedged else None
    finally:
        # an exception anywhere above must not orphan the worker OS
        # processes (their reconnect loops would outlive the phase
        # child) — every teardown is guarded and best-effort
        for closer in (lambda: asc and asc.stop(),
                       lambda: pool and pool.stop(),
                       lambda: gw and gw.stop(),
                       lambda: launcher and launcher.stop_all()):
            try:
                closer()
            except Exception:
                pass
    return out


def _phase_decode():
    """Stateful decode serving (ISSUE 18): the numbers behind the
    continuous-batching claim. One paged-KV DecodeEngine runs the same
    varied-length trace twice: CONTINUOUS (all sequences submitted
    up-front; iteration-level admit/retire keeps the batch full) vs
    STATIC emulation (groups of batch_size gated to completion — slots
    idle while the group straggler finishes). Reports aggregate
    `decode_tokens_per_sec` for both, their goodput ratio, the
    inter-token and time-to-first-token p50/p99 from the engine's
    always-on latency histograms, the streamed tokens/s for the same
    trace ACROSS the TCP wire (stok frames, safe codec), and the
    program-family size (must stay len(buckets) prefill + 1 step: the
    steady-state loop never recompiles)."""
    import numpy as np
    import jax
    from mxnet_tpu import profiler
    from mxnet_tpu.models.tiny_lm import TinyLMDecodeModel
    from mxnet_tpu.serving import (ModelServer, ServingFrontDoor,
                                   ServingClient, DecodeEngine)
    platform = jax.devices()[0].platform
    vocab, dim = 256, 64
    lm = TinyLMDecodeModel(vocab=vocab, dim=dim).engine_kwargs()
    batch = 4
    eng = DecodeEngine(**lm, name="bench", num_blocks=256,
                       batch_size=batch, max_seq_len=128,
                       prefill_buckets=(16,))
    rng = np.random.RandomState(0)
    n_seq = 32
    prompts = [[int(t) for t in rng.randint(1, vocab, rng.randint(3, 13))]
               for _ in range(n_seq)]
    # widely varied generation lengths: the regime where iteration-level
    # batching wins (a static batch idles its slots on the straggler)
    budgets = [int(b) for b in rng.randint(4, 33, size=n_seq)]
    wait_s = PHASE_BUDGET_S["decode"]
    eng.generate(prompts[0], max_new_tokens=4)        # warm the family
    profiler.latency_counters(reset=True, prefix="decode.bench.")

    # --- continuous: everything submitted up-front --------------------
    tic = time.monotonic()
    streams = [eng.submit(p, max_new_tokens=b)
               for p, b in zip(prompts, budgets)]
    toks_cont = sum(len(s.result_wait(wait_s)) for s in streams)
    wall_cont = time.monotonic() - tic
    lat = profiler.latency_counters(prefix="decode.bench.")
    intertok = lat.get("decode.bench.intertoken", {})
    ttft = lat.get("decode.bench.ttft", {})

    # --- static emulation: batch_size groups gated to completion ------
    tic = time.monotonic()
    toks_stat = 0
    for i in range(0, n_seq, batch):
        grp = [eng.submit(p, max_new_tokens=b)
               for p, b in zip(prompts[i:i + batch], budgets[i:i + batch])]
        toks_stat += sum(len(s.result_wait(wait_s)) for s in grp)
    wall_stat = time.monotonic() - tic

    # --- same trace streamed across the TCP wire ----------------------
    srv = ModelServer()
    srv.register_decode("bench", eng)
    fd = ServingFrontDoor(srv, port=0).start()
    cli = ServingClient("127.0.0.1", fd.port)
    try:
        tic = time.monotonic()
        sts = [cli.decode_async(p, model="bench", max_new_tokens=b)
               for p, b in zip(prompts, budgets)]
        toks_wire = sum(len(s.result_wait(wait_s)) for s in sts)
        wall_wire = time.monotonic() - tic
    finally:
        cli.close()
        fd.drain(timeout=30.0)
        srv.stop()

    # --- real transformer decode body (ISSUE 19) ----------------------
    # multi-layer multi-head decode over the SAME paged-KV engine:
    # flash-kernel prefill (tier resolved by MXNET_SERVING_DECODE_FLASH /
    # MXNET_TPU_MESH_KERNEL_TIER), chunked prefill so the long prompt in
    # the trace never stalls the continuous-batching step loop, and the
    # same program-family law (len(buckets) prefill + 1 step).
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              TransformerDecodeModel)
    from mxnet_tpu.parallel import kernel_tier_mode
    from mxnet_tpu.parallel.mesh_kernels import flash_mesh_roofline
    cfg = TransformerConfig(vocab_size=vocab, num_layers=2, num_heads=4,
                            d_model=64, max_len=128, block_k=16)
    model = TransformerDecodeModel(cfg, seed=0)
    tf_eng = DecodeEngine(name="bench_tf", num_blocks=256,
                          batch_size=batch, max_seq_len=128,
                          prefill_buckets=(16,), prefill_chunk=16,
                          **model.engine_kwargs())
    # 16 short prompts plus one past-the-bucket prompt that only the
    # chunked path can admit — proves the chunk seam under load
    tf_prompts = prompts[:16] + [[int(t) for t in
                                  rng.randint(1, vocab, 40)]]
    tf_budgets = budgets[:16] + [8]
    tf_eng.generate(tf_prompts[0], max_new_tokens=2)  # warm the family
    tic = time.monotonic()
    tf_streams = [tf_eng.submit(p, max_new_tokens=b)
                  for p, b in zip(tf_prompts, tf_budgets)]
    toks_tf = sum(len(s.result_wait(wait_s)) for s in tf_streams)
    wall_tf = time.monotonic() - tic
    tf_pf, tf_st = tf_eng.program_counts()
    tf_stats = tf_eng.stats()
    tf_eng.stop()
    # per-axis roofline of the prefill attention at the bucket shape,
    # under the dryrun's reference dp=4 x tp=2 mesh (analytic — shape-
    # only mesh stand-in, same figures a live mesh would report)

    class _RefMesh:
        shape = {"dp": 4, "tp": 2}
    tf_roofline = flash_mesh_roofline(
        (1, cfg.num_heads, 16, cfg.d_model // cfg.num_heads),
        _RefMesh(), itemsize=4, causal=True)

    cont_tps = toks_cont / wall_cont if wall_cont else 0.0
    stat_tps = toks_stat / wall_stat if wall_stat else 0.0
    pf, st = eng.program_counts()
    kv = eng.stats()["kv"]
    return {
        "decode_tokens_per_sec": round(cont_tps, 1),
        "decode_static_tokens_per_sec": round(stat_tps, 1),
        "decode_goodput_continuous_vs_static": round(
            cont_tps / stat_tps, 2) if stat_tps else None,
        "decode_intertoken_p50_ms": intertok.get("p50_ms"),
        "decode_intertoken_p99_ms": intertok.get("p99_ms"),
        "decode_ttft_p50_ms": ttft.get("p50_ms"),
        "decode_ttft_p99_ms": ttft.get("p99_ms"),
        "decode_stream_tokens_per_sec": round(
            toks_wire / wall_wire, 1) if wall_wire else 0.0,
        "decode_programs": "%d+%d" % (pf, st),
        "decode_kv_blocks_high_water": kv["blocks_high_water"],
        "decode_tf_tokens_per_sec": round(
            toks_tf / wall_tf, 1) if wall_tf else 0.0,
        "decode_tf_programs": "%d+%d" % (tf_pf, tf_st),
        "decode_tf_prefill_chunks": tf_stats.get("prefill_chunks", 0),
        "decode_kernel_tier": kernel_tier_mode(),
        "decode_tf_flash_engaged": model.flash_engaged,
        "decode_flash_roofline": tf_roofline,
        "decode_platform": platform,
    }


def _phase_fault_recovery():
    """Resilience under injected faults (ISSUE 9): the numbers that make
    the recovery claims measurable. (a) Replica kill mid-trace: one of
    two serving replicas starts failing every dispatch; the breaker must
    open, traffic must reroute, and the trace must account exactly —
    `fault_lost` (submitted - served - shed) MUST be 0, with
    `fault_reroute_ms` = wall time from the kill to the first
    failed-then-rerouted request resolving served. (b) Checkpoint I/O
    fault: a save hit by an injected write failure retries to a commit;
    the restored params must be BIT-exact (`ckpt_fault_bit_exact`), and
    `ckpt_recovery_ms` prices the retry against a clean save."""
    import shutil
    import tempfile
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving import ModelServer, DeadlineExceeded

    rng = np.random.RandomState(0)
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fr_fc0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fr_fc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    shapes, _, _ = sym.infer_shape(data=(8, 16))
    args = {n: mx.nd.array(rng.normal(0, 0.1, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}
    out = {}

    # -- (a) replica kill under load -----------------------------------
    faults.reset()
    profiler.fault_counters(reset=True)
    srv = ModelServer(breaker_threshold=3, breaker_cooldown_ms=5000.0)
    srv.register("fr", sym, args, ctx=mx.tpu(0), replicas=2, buckets=(8,),
                 max_delay_ms=1.0, warmup_shapes={"data": (8, 16)})
    x = rng.normal(0, 1, (1, 16)).astype(np.float32)
    n_req, kill_at = 120, 40
    futs, t_kill = [], None
    for i in range(n_req):
        if i == kill_at:
            t_kill = time.monotonic()
            faults.configure("serving.dispatch:replica=0:mode=async:"
                             "raise=OSError,replica killed")
        futs.append(srv.predict_async("fr", {"data": x},
                                      deadline_ms=2000.0))
        time.sleep(0.002)   # steady open-loop-ish trace
    served = shed = lost = retried = 0
    first_reroute = None
    for f in futs:
        try:
            f.result_wait(30.0)
            served += 1
            if f.attempts > 1:
                retried += 1
                if first_reroute is None or f.t_done < first_reroute:
                    first_reroute = f.t_done
        except DeadlineExceeded:
            shed += 1
        except Exception:
            lost += 1
    st = srv.stats()["fr"]
    faults.reset()
    srv.stop()
    out["fault_submitted"] = n_req
    out["fault_served"] = served
    out["fault_shed"] = shed
    out["fault_lost"] = lost
    out["fault_retried"] = retried
    out["fault_breaker_open"] = \
        st["versions"]["1"][0]["breaker"]["state"] == "open"
    out["fault_injected"] = profiler.fault_counters().get(
        "serving.dispatch", 0)
    if first_reroute is not None and t_kill is not None:
        out["fault_reroute_ms"] = round((first_reroute - t_kill) * 1e3, 2)

    # -- (b) checkpoint write fault ------------------------------------
    from mxnet_tpu import checkpoint as ckpt_mod
    from mxnet_tpu.checkpoint import CheckpointManager
    tmpdir = tempfile.mkdtemp(prefix="bench_fault_ckpt_")
    try:
        mgr = CheckpointManager(tmpdir)
        mgr._write_retry.base_delay_s = 0.001
        w = rng.normal(0, 1, (256, 256)).astype(np.float32)

        def timed_save(step, fault):
            faults.reset()
            if fault:
                faults.configure(
                    "checkpoint.write:count=1:raise=OSError,disk blip")
            t0 = time.monotonic()
            mgr.save(step, symbol=sym,
                     arg_params={"fr_w": mx.nd.array(w)}, blocking=True)
            faults.reset()
            return (time.monotonic() - t0) * 1e3
        clean_ms = timed_save(1, fault=False)
        faulted_ms = timed_save(2, fault=True)
        arg, _ = ckpt_mod.load_params(ckpt_mod.latest_checkpoint(tmpdir))
        out["ckpt_fault_bit_exact"] = bool(
            np.array_equal(arg["fr_w"].asnumpy(), w))
        out["ckpt_save_clean_ms"] = round(clean_ms, 2)
        out["ckpt_recovery_ms"] = round(faulted_ms, 2)
        out["ckpt_fault_retried"] = profiler.retry_counters().get(
            "checkpoint.write.recovery", 0)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    return out


def _phase_compile_cache():
    """Persistent-compile-cache cold start (ISSUE 14): the startup
    latency the unified ProgramBuilder seam buys. Two measurements, both
    cross-PROCESS (a restart, not an in-process cache hit):

    (a) cold vs warm compile wall-time — subprocess A warms a serving
        engine's bucket programs into a FRESH `MXNET_TPU_COMPILE_CACHE`
        dir (every compile pays XLA); subprocess B re-warms the same
        programs from disk. Acceptance: warm/cold <= 0.5 on the CPU
        host, with B's builder reporting persistent-cache-backed
        compiles and a bit-identical prediction.
    (b) worker warmup-to-admission — a real `ReplicaWorker` OS process
        (spawned through `LocalProcessLauncher`, joining a `FleetPool`
        gateway) timed from launch to admission (workers_alive), cold
        (fresh cache dir) vs warm (second launch, populated dir): the
        fleet scale-up latency the autoscaler pays per worker (PR 11),
        now mostly interpreter+import+disk instead of XLA.

    Reuses tools/compile_cache_smoke.py's child protocol and worker
    builder so CI gate and bench can never measure different code."""
    import shutil
    import tempfile
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    sys.path.insert(0, _HERE)
    import compile_cache_smoke as _cc

    out = {}
    # -- (a) cold vs warm compile wall-time, two fresh processes --------
    cache_dir = tempfile.mkdtemp(prefix="bench_cc_")
    wdir = tempfile.mkdtemp(prefix="bench_cc_worker_")
    try:
        env = dict(os.environ)
        env["MXNET_TPU_COMPILE_CACHE"] = cache_dir
        env["JAX_PLATFORMS"] = "cpu"
        # the bench harness shares a pre-warmed .jax_cache with its
        # children (and cpu_mesh_env pins a device-count flag): both
        # would contaminate the COLD measurement — the point is the
        # fresh dir above
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.pop("XLA_FLAGS", None)
        cold = _cc._run_child(env)
        warm = _cc._run_child(env)
        out["compile_cache_cold_ms"] = cold["warmup_ms"]
        out["compile_cache_warm_ms"] = warm["warmup_ms"]
        out["compile_cache_warm_cold_ratio"] = round(
            warm["warmup_ms"] / cold["warmup_ms"], 4) \
            if cold["warmup_ms"] else None
        out["compile_cache_cold_compiles"] = cold["compiles"]
        out["compile_cache_warm_persistent_hits"] = warm["persistent_hits"]
        out["compile_cache_bit_identical"] = (
            cold["pred_digest"] == warm["pred_digest"])

        # -- (b) worker warmup-to-admission, cold vs warm ---------------
        from mxnet_tpu.serving import (ModelServer, FleetPool,
                                       LocalProcessLauncher)
        # the launcher merges its env over THIS process's os.environ, so
        # the shared .jax_cache must be dropped here too or the "cold"
        # worker would warm-start from the committed bench cache
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        gw = pool = launcher = None
        try:
            import mxnet_tpu as mx
            gw = ModelServer()
            # admission is per-model: the pool only admits workers
            # offering a model the gateway serves, so the gateway
            # registers the same smoke net the worker builder does
            sym = _cc._net()
            gw.register(_cc.MODEL, sym, _cc._params(sym), ctx=mx.cpu(),
                        buckets=_cc.BUCKETS, max_delay_ms=0.5,
                        warmup_shapes={"data": _cc.DATA_SHAPE})
            pool = FleetPool(gw, port=0, heartbeat_s=0.25).start()
            launcher = LocalProcessLauncher(
                "127.0.0.1:%d" % pool.port,
                "compile_cache_smoke:build_worker",
                env={"PYTHONPATH": os.path.join(_HERE, "tools")
                     + os.pathsep + _HERE + os.pathsep
                     + os.environ.get("PYTHONPATH", ""),
                     "MXNET_TPU_COMPILE_CACHE": wdir,
                     "JAX_PLATFORMS": "cpu"})

            def admit(n_alive):
                t0 = time.monotonic()
                launcher.launch()
                deadline = t0 + 120.0
                while pool.stats()["workers_alive"] < n_alive:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            "compile_cache bench worker never admitted: "
                            "%s" % pool.stats())
                    time.sleep(0.02)
                return round((time.monotonic() - t0) * 1e3, 1)

            out["worker_admission_cold_ms"] = admit(1)   # wdir is empty
            out["worker_admission_warm_ms"] = admit(2)   # wdir populated
            out["worker_admission_warm_saved_ms"] = round(
                out["worker_admission_cold_ms"]
                - out["worker_admission_warm_ms"], 1)
        finally:
            for closer in (lambda: launcher and launcher.stop_all(),
                           lambda: pool and pool.stop(),
                           lambda: gw and gw.stop()):
                try:
                    closer()
                except Exception:
                    pass
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
        shutil.rmtree(wdir, ignore_errors=True)
    return out


def _phase_train_chaos():
    """Training-failure recovery cost (ISSUE 15): what the training
    supervisor's containment actually costs, measured through the same
    child driver as the `ci/run.py train_chaos_smoke` gate (tools/
    train_chaos_smoke.py) so gate and bench can never measure different
    code. Three numbers:

    (a) SIGKILL mid-epoch -> supervised auto-resume: the resumed fit's
        wall-time vs the uninterrupted twin's, gated on BIT-identical
        final params (crash-exact resume: cursor + shuffle-RNG chain +
        supervisor state all replayed from the manifest);
    (b) elastic ZeRO dp=2 -> dp=4 resume (the PR-7 cross-count restore
        driven end to end), same bit-parity gate;
    (c) NaN-injection recovery: a supervised fit with one poisoned step
        (train.nan fault) vs the same fit clean — the wall-time cost of
        skip-and-back-off containment, gated on the skip being exactly
        one step and the params staying finite."""
    import shutil
    import tempfile
    sys.path.insert(0, os.path.join(_HERE, "tools"))
    import train_chaos_smoke as _tc

    out = {}
    # -- (a) SIGKILL mid-epoch -> resume, bit-parity + wall-time --------
    res = _tc.sigkill_resume_variant("fp32")
    out["train_chaos_bit_identical"] = res["bit_identical"]
    out["train_chaos_clean_fit_s"] = res["clean_fit_s"]
    out["train_chaos_resume_fit_s"] = res["resume_fit_s"]
    if res["clean_fit_s"]:
        out["train_chaos_resume_ratio"] = round(
            res["resume_fit_s"] / res["clean_fit_s"], 3)

    # -- (b) elastic ZeRO resume under a changed replica count ----------
    el = _tc.elastic_zero_variant()
    out["train_chaos_elastic_bit_identical"] = el["bit_identical"]
    out["train_chaos_elastic_resume_fit_s"] = el["resume_fit_s"]

    # -- (c) NaN containment recovery wall-time -------------------------
    base = tempfile.mkdtemp(prefix="bench_tc_nan_")
    try:
        kw = dict(epochs=2, rows=64, batch=8, seed=7)
        t0 = time.monotonic()
        p = _tc._run(_tc.child_argv(ckpt=os.path.join(base, "ck_clean"),
                                    out=os.path.join(base, "clean.npz"),
                                    **kw))
        clean_s = time.monotonic() - t0
        assert p.returncode == 0, p.stderr.decode()[-2000:]
        t0 = time.monotonic()
        p = _tc._run(_tc.child_argv(ckpt=os.path.join(base, "ck_nan"),
                                    out=os.path.join(base, "nan.npz"),
                                    **kw),
                     env_extra={"MXNET_TPU_FAULT_SPEC":
                                "train.nan:count=3:raise=FaultInjected"})
        nan_s = time.monotonic() - t0
        assert p.returncode == 0, p.stderr.decode()[-2000:]
        with open(os.path.join(base, "nan.npz.json")) as f:
            sc = json.load(f)["supervisor"]
        assert sc["bad_steps"] == 1, \
            "poisoned step not skipped exactly once: %s" % sc
        import numpy as np
        fin = np.load(os.path.join(base, "nan.npz"))
        assert all(np.isfinite(fin[k]).all() for k in fin.files), \
            "NaN leaked into params"
        out["train_chaos_nan_clean_fit_s"] = round(clean_s, 2)
        out["train_chaos_nan_faulted_fit_s"] = round(nan_s, 2)
        out["train_chaos_nan_recovery_s"] = round(nan_s - clean_s, 2)
        out["train_chaos_nan_steps_skipped"] = sc["bad_steps"]
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


PHASES = {
    "probe": _phase_probe,
    "infer": _phase_infer,
    "train_fp32": _phase_train_fp32,
    "train_bf16": _phase_train_bf16,
    "jax_baseline": _phase_jax_baseline,
    "flash": _phase_flash,
    "io_train": _phase_io_train,
    "infer_int8": _phase_infer_int8,
    "train_big_batch": _phase_train_big_batch,
    "flash_parity": _phase_flash_parity,
    "cost": _phase_cost,
    "serving": _phase_serving,
    "serving_sla": _phase_serving_sla,
    "frontdoor": _phase_frontdoor,
    "fleet": _phase_fleet,
    "decode": _phase_decode,
    "fault_recovery": _phase_fault_recovery,
    "compile_cache": _phase_compile_cache,
    "train_chaos": _phase_train_chaos,
}


def _run_phases_in_process(names):
    """`--phase` / `--run`: this process imports jax itself (its parent, if
    any, stayed off it). The platform rule of main() holds here too, and an
    exception in a phase is the exit code."""
    import jax
    refusal = _platform_refusal(jax.devices()[0].platform)
    if refusal:
        sys.exit(refusal)
    out = {}
    for name in names:
        out.update(PHASES[name]())
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if "--phase" in sys.argv:
        _run_phases_in_process([sys.argv[sys.argv.index("--phase") + 1]])
    elif "--run" in sys.argv:
        # single-process mode (the CI smoke, which exports JAX_PLATFORMS=cpu)
        _run_phases_in_process(("infer", "train_fp32", "flash"))
    else:
        main()
