"""Fused optimizer-update kernel (kernels/opt_update.py): bit-parity with
the tree-map path (tpu_step prologue + optim_update.apply_update) across
all three tiers — pure-lax fallback, interpret-mode Pallas kernel, and the
tpu_step routing behind MXNET_TPU_FUSED_OPTUPDATE — plus the roofline
byte accounting bench gates the kernel on.

Parity is asserted JITTED-vs-JITTED (both routes trace as one program, so
XLA applies the same FMA fusions to both); that is exactly the contract the
flag toggles in production.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.kernels.opt_update import (fused_update_step,
                                          optupdate_ideal_bytes,
                                          optupdate_kernel_bytes,
                                          _kernel_eligible)
from mxnet_tpu.parallel.optim_update import apply_update, init_opt_state


def _make_tree(rng, dtype=jnp.float32):
    """Mixed leaf sizes: kernel-eligible (lane-aligned, big), lax-tier
    (tiny bias, odd-sized vector) — one update must handle all."""
    return {
        "w_big": jnp.asarray(rng.normal(0, 1, (1024, 128)), dtype),
        "w_conv": jnp.asarray(rng.normal(0, 1, (16, 8, 4, 4)), dtype),
        "b_tiny": jnp.asarray(rng.normal(0, 1, (10,)), dtype),
        "v_odd": jnp.asarray(rng.normal(0, 1, (103,)), dtype),
    }


def _hp(optimizer):
    if optimizer == "adam":
        return {"lr": 0.003, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
    return {"lr": 0.05, "momentum": 0.9}


def _reference_route(optimizer, hp, rescale, clip, wd):
    """tpu_step's exact tree-map sequence: rescale -> clip -> +wd*w ->
    apply_update."""
    def route(p, st, g, lr):
        g = {n: v * rescale for n, v in g.items()}
        if clip is not None:
            g = {n: jnp.clip(v, -clip, clip) for n, v in g.items()}
        g = {n: v + wd * p[n] for n, v in g.items()}
        return apply_update(optimizer, dict(hp, lr=lr), p, st, g)
    return route


def _init_state(optimizer, params, rng):
    st = init_opt_state(optimizer, params,
                        momentum=_hp(optimizer).get("momentum", 0.0))
    # non-zero state so momentum/adam paths have real history to fold
    if optimizer == "adam":
        st = {"m": {n: jnp.asarray(rng.normal(0, 0.01, v.shape), v.dtype)
                    for n, v in params.items()},
              "v": {n: jnp.asarray(rng.uniform(0, 1e-4, v.shape), v.dtype)
                    for n, v in params.items()},
              "t": jnp.asarray(3, jnp.int32)}
    elif st.get("mom") is not None:
        st = {"mom": {n: jnp.asarray(rng.normal(0, 0.1, v.shape), v.dtype)
                      for n, v in params.items()}}
    return st


@pytest.mark.parametrize("optimizer", ["sgd", "sgd_momentum", "adam"])
@pytest.mark.parametrize("clip", [None, 0.1])
def test_fused_lax_bitwise_parity(optimizer, clip):
    """The pure-lax fused tier is bit-identical to the tree-map route for
    every optimizer, with and without gradient clipping."""
    opt = "sgd" if optimizer.startswith("sgd") else optimizer
    rng = np.random.RandomState(0)
    params = _make_tree(rng)
    grads = _make_tree(np.random.RandomState(1))
    hp = _hp(opt)
    if optimizer == "sgd":
        hp["momentum"] = 0.0
    st = _init_state(opt, params, np.random.RandomState(2)) \
        if optimizer != "sgd" else {"mom": None}
    rescale, wd = 1.0 / 32, 1e-4

    ref = jax.jit(_reference_route(opt, hp, rescale, clip, wd))
    fused = jax.jit(lambda p, s, g, lr: fused_update_step(
        opt, dict(hp, lr=lr), p, s, g, rescale=rescale, clip=clip, wd=wd,
        use_pallas=False))
    lr = np.float32(hp["lr"])
    p_ref, s_ref = ref(params, st, grads, lr)
    p_fus, s_fus = fused(params, st, grads, lr)
    for a, b in zip(jax.tree_util.tree_leaves((p_ref, s_ref)),
                    jax.tree_util.tree_leaves((p_fus, s_fus))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _assert_within_operand_ulps(got, want, operands, ulps=4):
    """|got - want| <= `ulps` units in the last place AT THE MAGNITUDE OF THE
    LARGEST OPERAND, and all but 1% of elements bit-identical: the parity
    contract of kernels/opt_update.py for the interpret tier, where XLA:CPU
    contracts a*b + c into a fused multiply-add per fusion (one rounding
    per contraction; a raw ulp count of the result would blow up wherever
    the update cancels)."""
    got, want = np.asarray(got), np.asarray(want)
    scale = np.max([np.abs(np.asarray(o)) for o in operands]
                   + [np.abs(got), np.abs(want)], axis=0)
    bound = ulps * np.spacing(scale.astype(np.float32))
    assert (np.abs(got - want) <= bound).all(), \
        float((np.abs(got - want) / np.spacing(scale)).max())
    assert (got != want).mean() <= 0.01


@pytest.mark.parametrize("optimizer", ["sgd", "sgd_momentum", "adam"])
def test_fused_kernel_interpret_parity(optimizer):
    """The Pallas kernel body in interpret mode agrees with the jitted
    tree-map route to the contract's 4 operand-ulps. (On the TPU the
    compiled kernel and the lax leaf are bit-identical — chip_smoke.py's
    kernels phase measures that; here both sides are XLA:CPU programs.)"""
    opt = "sgd" if optimizer.startswith("sgd") else optimizer
    rng = np.random.RandomState(3)
    params = _make_tree(rng)
    grads = _make_tree(np.random.RandomState(4))
    hp = _hp(opt)
    if optimizer == "sgd":
        hp["momentum"] = 0.0
    st = _init_state(opt, params, np.random.RandomState(5)) \
        if optimizer != "sgd" else {"mom": None}
    rescale, wd = 1.0 / 32, 1e-4

    ref = jax.jit(_reference_route(opt, hp, rescale, None, wd))
    kern = jax.jit(lambda p, s, g, lr: fused_update_step(
        opt, dict(hp, lr=lr), p, s, g, rescale=rescale, wd=wd,
        use_pallas=False, interpret=True))
    lr = np.float32(hp["lr"])
    p_ref, s_ref = ref(params, st, grads, lr)
    p_k, s_k = kern(params, st, grads, lr)
    for name in params:
        operands = [params[name], grads[name]] + [
            slot[name] for slot in st.values() if isinstance(slot, dict)]
        _assert_within_operand_ulps(p_k[name], p_ref[name], operands)
        for key, slot in s_ref.items():
            if isinstance(slot, dict):
                _assert_within_operand_ulps(s_k[key][name], slot[name],
                                            operands)
    if "t" in s_ref:
        assert int(s_k["t"]) == int(s_ref["t"])


def test_kernel_eligibility_split():
    """Only lane-aligned f32 leaves big enough to amortize a dispatch take
    the kernel; the rest ride the lax tier (the same fused expression)."""
    rng = np.random.RandomState(6)
    tree = _make_tree(rng)
    assert _kernel_eligible(tree["w_big"])
    assert _kernel_eligible(tree["w_conv"])  # 2048 elems, 128-aligned
    assert not _kernel_eligible(tree["b_tiny"])
    assert not _kernel_eligible(tree["v_odd"])
    assert not _kernel_eligible(jnp.zeros((1024, 128), jnp.bfloat16))


def test_fused_step_multi_step_trajectory():
    """Parity holds over a multi-step trajectory (state feeds back), not
    just one update."""
    rng = np.random.RandomState(7)
    params = _make_tree(rng)
    hp = _hp("adam")
    st = init_opt_state("adam", params)
    ref = jax.jit(_reference_route("adam", hp, 1.0, None, 0.0))
    fus = jax.jit(lambda p, s, g, lr: fused_update_step(
        "adam", dict(hp, lr=lr), p, s, g, use_pallas=False, interpret=True))
    p_r, s_r = params, st
    p_f, s_f = params, st
    lr = np.float32(hp["lr"])
    for i in range(4):
        g = _make_tree(np.random.RandomState(10 + i))
        p_r, s_r = ref(p_r, s_r, g, lr)
        p_f, s_f = fus(p_f, s_f, g, lr)
    assert int(s_f["t"]) == 4
    for a, b in zip(jax.tree_util.tree_leaves((p_r, s_r)),
                    jax.tree_util.tree_leaves((p_f, s_f))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run_tpu_step(fused, optimizer="sgd", compute_dtype=None, n_steps=3,
                  clip=None):
    from mxnet_tpu.parallel.mesh import data_parallel_mesh
    from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(fc2, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.normal(0, 1, (32, 10)).astype(np.float32)
    y = (X[:, :4]).argmax(axis=1).astype(np.float32)
    mesh = data_parallel_mesh(jax.devices()[:1])
    hp = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8} \
        if optimizer == "adam" else None
    st = DataParallelTrainStep(sym, mesh, lr=0.05, momentum=0.9, wd=1e-4,
                               data_names=("data",),
                               label_names=("softmax_label",),
                               optimizer=optimizer, opt_hp=hp,
                               clip_gradient=clip,
                               compute_dtype=compute_dtype,
                               fused_optupdate=fused)
    st.init({"data": (32, 10), "softmax_label": (32,)}, seed=11)
    for _ in range(n_steps):
        st({"data": X, "softmax_label": y})
    return st


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_tpu_step_flag_bit_parity(optimizer):
    """MXNET_TPU_FUSED_OPTUPDATE on/off trains to bit-identical params and
    optimizer state through the real fused train step."""
    a = _run_tpu_step(False, optimizer=optimizer, clip=1.0)
    b = _run_tpu_step(True, optimizer=optimizer, clip=1.0)
    for x, yv in zip(jax.tree_util.tree_leaves((a.params, a.opt_state)),
                     jax.tree_util.tree_leaves((b.params, b.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(yv))


def test_tpu_step_flag_bit_parity_bf16_master_weights():
    """Multi-precision (bf16 compute, fp32 master weights): the fused
    route updates the fp32 masters bit-identically too."""
    a = _run_tpu_step(False, compute_dtype="bfloat16")
    b = _run_tpu_step(True, compute_dtype="bfloat16")
    for v in b.params.values():
        assert v.dtype == jnp.float32  # masters stay fp32
    for x, yv in zip(jax.tree_util.tree_leaves((a.params, a.opt_state)),
                     jax.tree_util.tree_leaves((b.params, b.opt_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(yv))


def test_tpu_step_env_flag_routes(monkeypatch):
    """The env flag (read at ctor time) selects the fused route."""
    monkeypatch.setenv("MXNET_TPU_FUSED_OPTUPDATE", "1")
    from mxnet_tpu.parallel.mesh import data_parallel_mesh
    from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                              name="fc"), name="softmax")
    st = DataParallelTrainStep(sym, data_parallel_mesh(jax.devices()[:1]),
                               lr=0.1, momentum=0.9)
    assert st.fused_optupdate
    monkeypatch.setenv("MXNET_TPU_FUSED_OPTUPDATE", "0")
    st = DataParallelTrainStep(sym, data_parallel_mesh(jax.devices()[:1]),
                               lr=0.1, momentum=0.9)
    assert not st.fused_optupdate


def test_optupdate_byte_accounting():
    """Roofline accounting: ideal = (reads+writes) x param bytes per
    optimizer family; the kernel DMA schedule lands within a few percent
    of ideal (padded tail blocks + the SMEM scalar) and far below the
    tree-map's pre-fusion traffic."""
    params = {"w": jnp.zeros((1024, 128), jnp.float32),
              "b": jnp.zeros((10,), jnp.float32)}
    pbytes = (1024 * 128 + 10) * 4
    st_mom = init_opt_state("sgd", params, momentum=0.9)
    assert optupdate_ideal_bytes("sgd", params) == 3 * pbytes
    assert optupdate_ideal_bytes("sgd", params, st_mom) == 5 * pbytes
    assert optupdate_ideal_bytes("adam", params) == 7 * pbytes
    k = optupdate_kernel_bytes("sgd", params, st_mom)
    ideal = optupdate_ideal_bytes("sgd", params, st_mom)
    assert ideal <= k < 1.05 * ideal
