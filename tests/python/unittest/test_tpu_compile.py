"""The TPU compiler as a tier-1 guard: the kernels of the main path compiled
at their real widths for a DESCRIBED v5e (no chip attached, nothing runs).

What interpret mode cannot show — a slice the tiling rejects, more VMEM than
a kernel may use — the compiler refuses here, at no chip time. Every case
asserts the Pallas kernel is in the compiled text (`tpu_custom_call`).

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, xdist workers all import this
file, and only the worker that runs it may make the call. All cases stay in
this one file (a second file could land on another worker, whose fixture
would then skip), compile in this process, and keep the persistent compile
cache off (a described-device executable cannot be read back from it).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.kernels.flash_attention import (flash_attention,
                                               flash_attention_with_lse)
from mxnet_tpu.kernels.opt_update import fused_update_step


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def program_defaults():
    """Compile as a user's process would: persistent cache off (see above),
    and the matmul precision JAX ships with — conftest.py raises it to
    "highest" for the numeric-gradient suites, and Mosaic refuses an fp32
    contraction on the kernels' bf16 operands."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    jax.config.update("jax_default_matmul_precision", prev_precision)
    cc.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _flash_fwd_bwd(variant, block_q, block_k):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, block_q=block_q,
                              block_k=block_k, use_pallas=True,
                              variant=variant)
        return jnp.sum(out.astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("variant,block_q,block_k",
                         [("stream", 1024, 512), ("grid", 512, 512)])
def test_flash_fwd_bwd_compiles_at_bench_shape(one_chip, variant, block_q,
                                               block_k):
    """[4,8,4096,128] bf16 causal, the production block sizes: the forward
    kernel and the two backward kernels (dq, dk/dv) all reach Mosaic."""
    q = jax.ShapeDtypeStruct((4, 8, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile(_flash_fwd_bwd(variant, block_q, block_k), q, q, q)
    assert text.count("tpu_custom_call") >= 3


def _offset_fwd_bwd(block_q, block_k, variant="stream"):
    def loss(q, k, v, offs):
        out, lse = flash_attention_with_lse(
            q, k, v, offs, 1.0 / np.sqrt(q.shape[-1]), True, block_q,
            block_k, False, variant)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("name,q_shape,k_shape,block_q,block_k", [
    # DecodeEngine chunked prefill at GPT-2-small widths: a 256-token chunk
    # against the sequence's 1024 gathered cache positions, head dim 64
    ("decode_prefill", (1, 12, 256, 64), (1, 12, 1024, 64), 256, 512),
    # one ring-attention step: a 2048-token local block, head dim 128
    ("ring_step", (1, 8, 2048, 128), (1, 8, 2048, 128), 512, 512),
])
def test_offset_kernel_compiles(one_chip, name, q_shape, k_shape, block_q,
                                block_k):
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct(k_shape, jnp.bfloat16, sharding=one_chip)
    offs = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    _compile(_offset_fwd_bwd(block_q, block_k), q, k, k, offs)


@pytest.mark.parametrize("optimizer", ["sgd_momentum", "adam"])
def test_fused_update_kernel_compiles(one_chip, optimizer):
    """(1000, 2048) — ResNet-50's classifier weight: rows do not divide the
    kernel's 512-row block, so the ragged last grid step is compiled too."""
    leaf = jax.ShapeDtypeStruct((1000, 2048), jnp.float32, sharding=one_chip)
    tree = {"w": leaf}
    if optimizer == "adam":
        hp = {"lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8}
        state = {"m": tree, "v": tree,
                 "t": jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)}
        opt = "adam"
    else:
        hp = {"lr": 0.05, "momentum": 0.9}
        state = {"mom": tree}
        opt = "sgd"
    _compile(lambda p, s, g: fused_update_step(
        opt, hp, p, s, g, rescale=1.0 / 32, wd=1e-4, use_pallas=True),
        tree, state, tree)


def _flash_fwd(variant):
    return lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=512, block_k=512, use_pallas=True,
        variant=variant)


def test_stream_compiles_at_8k(one_chip):
    q = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    _compile(_flash_fwd("stream"), q, q, q)


def test_stream_is_refused_for_vmem_at_16k(one_chip):
    """`stream` keeps the whole K/V sequence resident in VMEM: at 16384
    positions that is past the kernel's 16 MiB scoped limit and the
    compiler says so. `stream` is still the default variant and nothing
    picks `grid` from the length (ROADMAP C5) — this pins the limit until
    something does."""
    q = jax.ShapeDtypeStruct((1, 8, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)
    with pytest.raises(Exception, match="(?i)vmem"):
        jax.jit(_flash_fwd("stream")).lower(q, q, q).compile()


def test_grid_compiles_at_16k(one_chip):
    q = jax.ShapeDtypeStruct((1, 8, 16384, 128), jnp.bfloat16,
                             sharding=one_chip)
    _compile(_flash_fwd("grid"), q, q, q)


def test_latent_prefill_attention_compiles_at_published_widths(one_chip):
    """models/moe_mla.py's prefill attention at openPangu-Ultra-MoE's head
    widths: 128 heads of 192 (keys) / 128 (values), zero-padded to 256
    lanes, a 1,024-token chunk against the 4,096 positions of a sequence's
    table (keys and values expanded from its latent rows), through the
    grid-variant offset kernel."""
    from mxnet_tpu.models.moe_mla import MoEMLAConfig, _attend_expanded
    cfg = MoEMLAConfig(
        hidden_size=7680, num_hidden_layers=1, first_k_dense_replace=1,
        num_attention_heads=128, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        intermediate_size=18432, moe_intermediate_size=2048,
        n_routed_experts=256, n_shared_experts=1, num_experts_per_tok=8,
        routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25.6e6,
        vocab_size=19200)
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,      # noqa: E731
                                         sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile(
        lambda w, q, rows, s: _attend_expanded(cfg, w, q, rows, s,
                                               True, False),
        sd(512, 128 * 256), sd(1024, 128, 192), sd(4096, 640), start)
    assert "mx_flash_fwd_offs_grid" in text


def test_grouped_expert_product_compiles_at_published_widths(one_chip):
    """parallel/moe.py::routed_experts for 16 held experts of 7680 x 2048
    at a decode step's 256 tokens x top-8: the grouped product reaches the
    TPU's own ragged kernel (no dense [T, E, C] dispatch)."""
    from mxnet_tpu.parallel.moe import routed_experts
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,      # noqa: E731
                                         sharding=one_chip)
    params = {"router": sd(7680, 256), "experts_gate": sd(16, 7680, 2048),
              "experts_up": sd(16, 7680, 2048),
              "experts_down": sd(16, 2048, 7680)}
    text = _compile(lambda p, x: routed_experts(
        p, x, held=(0, 16), top_k=8, scale=2.5), params, sd(256, 7680))
    assert "ragged" in text.lower()


@pytest.mark.parametrize("count,d,f", [(16, 7680, 2048), (32, 2304, 1024)],
                         ids=["pangu", "kimi"])
def test_grouped_experts_kernel_compiles_at_a_pieces_size(one_chip, count, d,
                                                          f):
    """parallel/moe.py::routed_experts on the kernel tier at a 1,024-token
    prefill piece x top-8, for pangu's 16 held experts of 7680 x 2048 and
    kimi's 32 of 2304 x 1024: every capacity of sorted rows is two
    `mx_grouped_experts` calls (gate-and-up, down) whose whole-contraction weight blocks fit the kernel's VMEM, every form hands
    back ``[1024, d]``, and no ``[T, top_k, d]`` float32 gather of a
    token's rows is left in the program. The capacities follow the held
    share of the router's width: 640 / 1,024 / 8,192 sorted rows for 16 of
    256, 1,280 / 2,048 / 8,192 for 32."""
    from mxnet_tpu.parallel.moe import _held_caps, routed_experts
    sd = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16,      # noqa: E731
                                         sharding=one_chip)
    params = {"router": sd(d, 256), "experts_gate": sd(count, d, f),
              "experts_up": sd(count, d, f), "experts_down": sd(count, f, d)}
    text = _compile(lambda p, x: routed_experts(
        p, x, held=(0, count), top_k=8, scale=2.5, use_pallas=True),
        params, sd(1024, d))
    calls = [ln for ln in text.splitlines()
             if "mx_grouped_experts" in ln and "custom-call(" in ln]
    assert _held_caps(8192, count / 256) == {
        16: [640, 1024, 8192], 32: [1280, 2048, 8192]}[count]
    assert len(calls) == 2 * 3, len(calls)
    assert "f32[1024,8,%d]" % d not in text
    assert "ragged" not in text.lower()


def test_grouped_experts_kernel_compiles_as_an_ep_share(topo):
    """The `shard_map` body over four described chips: each share's two
    `mx_grouped_experts` calls state what their results vary over (the
    `ep` axis), and the partial results meet in one all-reduce."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel.moe import routed_experts
    mesh = Mesh(np.array(topo.devices), ("ep",))
    sd = lambda spec, *s: jax.ShapeDtypeStruct(                # noqa: E731
        s, jnp.bfloat16, sharding=NamedSharding(mesh, spec))
    params = {"router": sd(P(), 2304, 256),
              "experts_gate": sd(P("ep"), 32, 2304, 1024),
              "experts_up": sd(P("ep"), 32, 2304, 1024),
              "experts_down": sd(P("ep"), 32, 1024, 2304)}
    specs = {k: v.sharding.spec for k, v in params.items()}

    def body(p, x):
        part, counts, _ = routed_experts(p, x, held=(0, 8), top_k=8,
                                         scale=2.446, axis_name="ep",
                                         use_pallas=True)
        return part, counts
    text = _compile(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P()), out_specs=(P(), P("ep"))),
        params, sd(P(), 256, 2304))
    assert "mx_grouped_experts" in text and "all-reduce" in text


@pytest.mark.parametrize("cell", ["kimi", "pangu"])
def test_latent_prefill_piece_runs_its_experts_through_the_grouped_kernel(
        one_chip, cell):
    """The two latent cells' 1,024-token prefill programs at the served
    sizes, on the kernel tier: every expert layer's routed product is the
    grouped kernel (two `mx_grouped_experts` calls for each of its three
    capacities), no conditional hands a ``[1024, 8, d]`` float32 back (the
    parent's four heaviest device operations, PERF.md PR 37), and the
    program's temporaries are under the parent's (counted, PR 37)."""
    model, e, _ = _latent_cell(cell)
    on_chip = lambda t: jax.tree_util.tree_map(                 # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    B, bs = e["batch_size"], e["block_size"]
    mb = e["max_seq_len"] // bs
    cache = on_chip(model.cache_spec(e["num_blocks"], bs, B))
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d,               # noqa: E731
                                           sharding=one_chip)
    compiled = jax.jit(model.prefill_fn, donate_argnums=(1,)).lower(
        on_chip(model.params), cache, sd((1024,), jnp.int32),
        sd((), jnp.int32), sd((), jnp.int32), sd((mb,), jnp.int32),
        sd((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines()
             if "mx_grouped_experts" in ln and "custom-call(" in ln]
    layers = sum("router" in lp for lp in model.params["layers"])
    assert len(calls) == layers * 3 * 2, len(calls)
    assert "f32[1024,8,%d]" % model.cfg.hidden_size not in text
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "pangu": 831837696, "kimi": 372275200}[cell]


def test_gpt2_decode_step_keeps_heads_in_lanes(one_chip):
    """models/transformer.py's decode step at the benchmark cell's shapes
    (64 rows, tables of 64 blocks of 16, 12 heads of 64, two layers): the
    compiled program holds no head-split view of a table's positions (the
    (8,128) tile pads a minor ``(12, 64)`` pair from 768 lanes to 2,048)
    and no whole-table buffer: its temporaries stay far under one row
    block's gathered table. No Pallas kernel: the walk is plain XLA."""
    import re
    from mxnet_tpu.kernels.paged_attention import walk_sizes
    from mxnet_tpu.models.transformer import (
        TransformerConfig, TransformerDecodeModel, _WALK_ROWS, _WALK_SPAN)
    cfg = TransformerConfig(vocab_size=1024, num_layers=2, num_heads=12,
                            d_model=768, d_ff=3072, max_len=1024)
    B, mb, bs = 64, 64, 16
    rb, cb = walk_sizes(B, mb, bs, _WALK_ROWS, _WALK_SPAN)
    on_chip = lambda a: jax.ShapeDtypeStruct(                  # noqa: E731
        a.shape, a.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: TransformerDecodeModel(cfg, flash="off").params))
    model = TransformerDecodeModel(cfg, params=params, flash="off")
    cache = jax.tree_util.tree_map(on_chip, model.cache_spec(1729, bs, B))
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d,               # noqa: E731
                                           sharding=one_chip)
    compiled = jax.jit(model.step_fn, donate_argnums=(1,)).lower(
        params, cache, sd((B,), jnp.int32), sd((B,), jnp.int32),
        sd((B, mb), jnp.int32), sd((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "f32[%d,%d,768]" % (rb, cb * bs) in text      # a piece, as gathered
    for dims in re.findall(r"f32\[([0-9,]+),12,64\]", text):
        positions = int(np.prod([int(d) for d in dims.split(",")]))
        assert positions < rb * cb * bs, "head-split buffer f32[%s,12,64]" % dims
    assert compiled.memory_analysis().temp_size_in_bytes \
        < rb * mb * bs * 768 * 4


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
def test_kda_step_kernel_compiles_in_place_at_published_widths(one_chip,
                                                               state_dtype):
    """`mx_kda_step` at Kimi-Linear's widths (32 heads of 128 x 128, 256
    slots, six layers' pool): the packed tile's transpose, the per-head lane
    slices and a row's 2 MB block pass Mosaic; the 3.2 GB pool is aliased to
    the output, never copied (temporaries stay in the megabytes)."""
    from mxnet_tpu.kernels import kda
    L, B, H, dk = 6, 256, 32, 128
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(     # noqa: E731
        s, d, sharding=one_chip)

    def step(state, q, k, v, g, beta, active):
        return kda.kda_step(state, 2, q, k, v, g, beta, active,
                            use_pallas=True)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        sd((L, B, H, dk, dk), state_dtype), sd((B, H, dk)), sd((B, H, dk)),
        sd((B, H, dk)), sd((B, H, dk)), sd((B, H)),
        sd((B,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    pool = L * B * H * dk * dk * jnp.dtype(state_dtype).itemsize
    assert mem.alias_size_in_bytes == pool
    assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("heads,mb,layers,blocks", [
    (128, 256, 5, 29258), (32, 512, 2, 50717)], ids=["pangu", "kimi"])
def test_paged_latent_attention_compiles_at_the_cells_shapes(
        one_chip, heads, mb, layers, blocks):
    """`mx_paged_latent_attn` at the two latent cells' shapes (256 rows of
    128 or 32 heads over 640-wide bfloat16 rows, tables of 256 or 512 pages
    of 16, the whole table in scalar memory): the page copies, the merged
    ``(pages, 16)`` view of a chunk, the two score products (the latent
    query against a row's first 512 numbers, the rotary one against the 64
    after them) and the lane slice of the values pass Mosaic, as do the new
    row's select into its page and the page's copy back; the pool is
    aliased to the output, never copied, and nothing but the rows' chain is
    made beside it."""
    from mxnet_tpu.kernels.paged_attention import paged_latent_attention
    B = 256
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d,               # noqa: E731
                                           sharding=one_chip)

    def attend(q, q_rope, new_rows, pool, positions, tables, active):
        return paged_latent_attention(q, q_rope, new_rows, pool, 1,
                                      positions, tables, active,
                                      sm_scale=0.0722)
    compiled = jax.jit(attend, donate_argnums=(3,)).lower(
        sd((B, heads, 512), jnp.bfloat16), sd((B, heads, 64), jnp.bfloat16),
        sd((B, 640), jnp.bfloat16),
        sd((layers, blocks, 16, 640), jnp.bfloat16), sd((B,), jnp.int32),
        sd((B, mb), jnp.int32), sd((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mx_paged_latent_attn" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == layers * blocks * 16 * 640 * 2
    assert mem.temp_size_in_bytes < 1 << 20


def _latent_cell(name):
    """``(model, engine geometry, latent layers)`` of a latent cell as the
    benchmark builds it, on the kernel tier, parameters as shapes."""
    import json
    import os
    cells = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         "..", "..", "benchmark", "cells")
    config, traffic = {
        "kimi": ("kimi_linear_ep8.json", "decode_batch_longgen.json"),
        "pangu": ("pangu_umoe_ep16.json", "decode_batch_long.json")}[name]
    with open(os.path.join(cells, "configs", config)) as f:
        config = json.load(f)
    with open(os.path.join(cells, "traffic", traffic)) as f:
        engine = json.load(f)["engine"]
    if name == "kimi":
        from mxnet_tpu.models.kimi_linear import (KimiLinearConfig,
                                                  KimiLinearDecodeModel,
                                                  init_kimi_linear)
        cfg = KimiLinearConfig.from_dict(config)
        init, Model = init_kimi_linear, KimiLinearDecodeModel
        latent = len(cfg.full_attn_layers)
    else:
        from mxnet_tpu.models.moe_mla import (MoEMLAConfig,
                                              MoEMLADecodeModel,
                                              init_moe_mla)
        cfg = MoEMLAConfig.from_dict(config)
        init, Model = init_moe_mla, MoEMLADecodeModel
        latent = cfg.num_hidden_layers
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0),
                                         jnp.bfloat16))
    return Model(cfg, params=params, flash="on"), engine, latent


def _instructions(text):
    """``{name: line}`` of every instruction in a compiled program's text."""
    import re
    out = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", ln)
        if m:
            out[m.group(1)] = ln
    return out


def _kernel_neighbours(text, kernel, operand):
    """For every call of the Pallas kernel named ``kernel``: the instruction
    that makes its ``operand``-th operand and those that read its first
    result, bitcasts looked through (they move no byte)."""
    import re
    lines = _instructions(text)
    users = {}
    for name, ln in lines.items():
        for used in set(re.findall(r"(%[\w.\-]+)(?=[,)])",
                                   ln.split("=", 1)[1])):
            users.setdefault(used, []).append(name)

    def made_by(name):
        while " bitcast(" in lines[name]:
            name = re.search(r" bitcast\((%[\w.\-]+)\)",
                             lines[name]).group(1)
        return name

    def read_by(name):
        out = []
        for u in users.get(name, []):
            out += read_by(u) if " bitcast(" in lines[u] else [u]
        return out

    found = []
    for name, ln in lines.items():
        if kernel not in ln or "custom-call(" not in ln:
            continue
        args = re.search(r"custom-call\((.*?)\), custom_call_target",
                         ln).group(1)
        args = [a.strip() for a in re.sub(r"/\*index=\d+\*/", "",
                                          args).split(",")]
        result = [u for u in users.get(name, [])
                  if "index=0" in lines[u] and "get-tuple-element(" in
                  lines[u]]
        found.append((lines[made_by(args[operand])],
                      [lines[r] for g in result for r in read_by(g)]))
    return found


def _relayout(ln):
    """A copy or a select: an instruction that moves a result as it is."""
    name, rhs = ln.split("=", 1)
    return " copy(" in rhs.split("metadata=")[0] or "select" in name


@functools.lru_cache(maxsize=None)
def _latent_step(cell, one_chip):
    """A latent cell's decode step compiled at the served size, on the
    kernel tier: ``(model, cache, compiled)``."""
    model, e, _ = _latent_cell(cell)
    on_chip = lambda t: jax.tree_util.tree_map(                 # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    params = on_chip(model.params)
    B, bs = e["batch_size"], e["block_size"]
    mb = e["max_seq_len"] // bs
    cache = on_chip(model.cache_spec(e["num_blocks"], bs, B))
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d,               # noqa: E731
                                           sharding=one_chip)
    compiled = jax.jit(model.step_fn, donate_argnums=(1,)).lower(
        params, cache, sd((B,), jnp.int32), sd((B,), jnp.int32),
        sd((B, mb), jnp.int32), sd((B,), jnp.bool_)).compile()
    return model, cache, compiled


@pytest.mark.parametrize("cell", ["kimi", "pangu"])
def test_latent_step_reads_its_pages_in_place_and_rematerialises_no_pool(
        one_chip, cell):
    """The two latent cells' decode steps at the served sizes (256 slots,
    tables of 512 and 256 pages), on the kernel tier.

    No instruction the compiler rematerialised reads or writes a donated
    pool. A chain of in-place updates of Kimi-Linear's tail pool, a layer at
    a time, was rematerialised at this size and read the pool AFTER it had
    been overwritten: wrong tokens on the chip, invisible to every CPU test
    (PERF.md, PR 34). Every layer now reads the pools as they came in and
    the program writes the tails once.

    Every latent layer's write and walk is ONE `mx_paged_latent_attn` that
    takes the pool whole and hands it on: the latent pool is touched by
    that chain of aliased calls and by nothing of XLA's (a scatter a layer
    beside the kernel's reads WAS rematerialised here, PERF.md PR 35), no
    gathered piece of 640-wide rows is left in the program (the lax walk's
    was ``[32, 512, 640]``), and its temporaries are under the lax
    step's."""
    import re
    _, e, latent = _latent_cell(cell)
    B = e["batch_size"]
    model, cache, compiled = _latent_step(cell, one_chip)
    text = compiled.as_text()
    if cell == "kimi":
        assert text.count("mx_kda_step") >= len(model.cfg.kda_layers)
    pools = ["[%s]" % ",".join(str(n) for n in p.shape)
             for p in jax.tree_util.tree_leaves(cache)]
    again = [ln for ln in text.splitlines() if ".remat" in ln.split("=")[0]
             and any(p in ln for p in pools)]
    assert not again, again[:2]
    calls = [ln for ln in text.splitlines()
             if "mx_paged_latent_attn" in ln and "custom-call(" in ln]
    assert len(calls) == latent, len(calls)
    # XLA itself writes nothing into the latent pool any more
    latent = "bf16" + pools[0]
    assert not [ln for ln in text.splitlines()
                if latent in ln and "scatter" in ln]
    # 640-wide and three-dimensional: the new rows only (the queries go in
    # as a 512-wide latent part and a 64-wide rotary one)
    pieces = set(re.findall(r"bf16\[\d+,\d+,640\]", text))
    assert pieces <= {"bf16[%d,1,640]" % B}, pieces
    assert compiled.memory_analysis().temp_size_in_bytes < {
        "pangu": 280950272, "kimi": 331771392}[cell]


@pytest.mark.parametrize("cell", ["kimi", "pangu"])
def test_latent_step_hands_its_kernel_rows_and_takes_its_result_as_it_is(
        one_chip, cell):
    """The two latent cells' decode steps at the served sizes: every
    `mx_paged_latent_attn` takes its latent queries without a 640-wide
    padded copy and hands ``u``, in the pool's bfloat16 and rows-major,
    straight to the per-head value product: no select over ``[rows, heads,
    512]`` (the kernel writes an inactive row's zeros itself) and no copy
    after the call. (Before the call XLA still turns the per-head query
    product's ``[H][r][B]`` into rows, a copy the padded query fusion held
    before; PERF.md §7.5.)"""
    model, cache, compiled = _latent_step(cell, one_chip)
    text = compiled.as_text()
    B, H = _latent_cell(cell)[1]["batch_size"], model.cfg.num_attention_heads
    assert "bf16[%d,%d,640]" % (B, H) not in text
    assert not [ln for name, ln in _instructions(text).items()
                if "select" in name and "[%d,%d,512]" % (B, H) in ln]
    found = _kernel_neighbours(text, "mx_paged_latent_attn", 6)
    assert len(found) == (len(model.cfg.full_attn_layers) if cell == "kimi"
                          else model.cfg.num_hidden_layers)
    for made, read in found:
        assert "640]" not in made.split("=", 1)[1][:40], made[:160]
        assert read and not [ln for ln in read if _relayout(ln)], read


def _eva_cell():
    """``(model, engine geometry)`` of the EvaByte cell as the benchmark
    builds it, on the kernel tier, parameters as shapes."""
    import json
    import os
    from mxnet_tpu.models.evabyte import (EvaByteConfig, EvaByteDecodeModel,
                                          init_evabyte)
    cells = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         "..", "..", "benchmark", "cells")
    with open(os.path.join(cells, "configs", "evabyte_l8.json")) as f:
        cfg = EvaByteConfig.from_dict(json.load(f))
    with open(os.path.join(cells, "traffic", "decode_batch_bytes.json")) as f:
        engine = json.load(f)["engine"]
    params = jax.eval_shape(lambda: init_evabyte(cfg, jax.random.PRNGKey(0),
                                                 jnp.bfloat16))
    return EvaByteDecodeModel(cfg, params=params, flash="on"), engine


@pytest.mark.parametrize("program", ["step", 256, 1024])
def test_evabyte_programs_write_each_pool_once_and_copy_none(one_chip,
                                                             program):
    """The EvaByte cell's decode step and prefill buckets at the served
    sizes (48 slots, tables of 192 entries, twin pools of 5.7 GB), on the
    kernel tier. Both pools are donated and come back in place; no
    instruction the compiler rematerialised touches one and none copies
    one (every layer reads the pools as they came in, and the rows of all
    layers go in at the program's end: PERF.md PR 34's rule). The step's
    attention is ONE `mx_eva_paged_attn` a layer, and its temporaries are
    two hundredths of a pool."""
    model, e = _eva_cell()
    on_chip = lambda t: jax.tree_util.tree_map(                 # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    B, bs = e["batch_size"], e["block_size"]
    mb = max(stop for _, stop in model.cache_pages(e["max_seq_len"]))
    assert mb == 128 + 64
    cache = on_chip(model.cache_spec(e["num_blocks"], bs, B))
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d,               # noqa: E731
                                           sharding=one_chip)
    if program == "step":
        compiled = jax.jit(model.step_fn, donate_argnums=(1,)).lower(
            on_chip(model.params), cache, sd((B,), jnp.int32),
            sd((B,), jnp.int32), sd((B, mb), jnp.int32),
            sd((B,), jnp.bool_)).compile()
    else:
        compiled = jax.jit(model.prefill_fn, donate_argnums=(1,)).lower(
            on_chip(model.params), cache, sd((program,), jnp.int32),
            sd((), jnp.int32), sd((), jnp.int32), sd((mb,), jnp.int32),
            sd((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    pool = "bf16[%s]" % ",".join(str(n) for n in cache["k"].shape)
    touched = [ln for ln in text.splitlines() if pool in ln]
    assert not [ln for ln in touched if ".remat" in ln.split("=")[0]]
    assert not [ln for ln in touched
                if pool in ln.split("=", 1)[-1].split("(")[0]
                and " copy(" in ln]
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(cache["k"].shape))
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // (50 if program == "step"
                                                   else 15)
    if program == "step":
        calls = [ln for ln in text.splitlines()
                 if "mx_eva_paged_attn" in ln and "custom-call(" in ln]
        assert len(calls) == model.cfg.num_hidden_layers


@functools.lru_cache(maxsize=None)
def _motif_step(one_chip):
    """The Motif-3 cell's decode step compiled at the served size, on the
    kernel tier: ``(cfg, engine geometry, cache, compiled)``."""
    import json
    import os
    from mxnet_tpu.models.motif import (MotifConfig, MotifDecodeModel,
                                        init_motif)
    cells = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         "..", "..", "benchmark", "cells")
    with open(os.path.join(cells, "configs", "motif3_ep8.json")) as f:
        cfg = MotifConfig.from_dict(json.load(f))
    with open(os.path.join(cells, "traffic",
                           "decode_batch_reason.json")) as f:
        e = json.load(f)["engine"]
    params = jax.eval_shape(lambda: init_motif(cfg, jax.random.PRNGKey(0),
                                               jnp.bfloat16))
    model = MotifDecodeModel(cfg, params=params, flash="on")
    on_chip = lambda t: jax.tree_util.tree_map(                 # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    B, bs = e["batch_size"], e["block_size"]
    mb = e["max_seq_len"] // bs
    cache = on_chip(model.cache_spec(e["num_blocks"], bs, B))
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d,               # noqa: E731
                                           sharding=one_chip)
    compiled = jax.jit(model.step_fn, donate_argnums=(1,)).lower(
        on_chip(model.params), cache, sd((B,), jnp.int32),
        sd((B,), jnp.int32), sd((B, mb), jnp.int32),
        sd((B,), jnp.bool_)).compile()
    return cfg, e, cache, compiled


def test_motif_step_writes_its_rings_in_place_and_rematerialises_no_pool(
        one_chip):
    """The Motif-3 cell's decode step at the served size (512 slots, tables
    of 384 pages, the two full layers' latent pool and the three window
    layers' rings), on the kernel tier. Each ring is written by its
    `mx_window_latent_attn` call alone, a chain of aliased calls that takes
    the pool whole and hands it on: no scatter of XLA's into it, and no
    instruction the compiler rematerialised touches a donated pool (three
    XLA scatters a step into one pool beside the kernels' reads are what
    the one-write rule forbids). The full layers walk their pages in
    `mx_paged_latent_attn`; both pools come back in place."""
    cfg, _, cache, compiled = _motif_step(one_chip)
    text = compiled.as_text()
    pools = {k: "bf16[%s]" % ",".join(str(n) for n in p.shape)
             for k, p in cache.items()}
    lines = text.splitlines()
    assert not [ln for ln in lines if ".remat" in ln.split("=")[0]
                and any(p in ln for p in pools.values())]
    calls = lambda name: [ln for ln in lines                    # noqa: E731
                          if name in ln and "custom-call(" in ln]
    assert len(calls("mx_window_latent_attn")) == cfg.window_layers == 3
    assert len(calls("mx_paged_latent_attn")) == cfg.full_layers == 2
    assert not [ln for ln in lines if any(p in ln for p in pools.values())
                and ("scatter" in ln or " copy(" in ln)]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == sum(
        int(np.prod(p.shape)) * 2 for p in cache.values())
    assert mem.temp_size_in_bytes < 300 << 20


@pytest.mark.parametrize("kernel,operand", [
    ("mx_paged_latent_attn", 6), ("mx_window_latent_attn", 5)],
    ids=["full", "window"])
def test_motif_step_hands_its_kernels_heads_major_and_copies_neither_way(
        one_chip, kernel, operand):
    """The Motif-3 cell's decode step at the served size: each GDLA kernel
    takes its latent queries ``[80, 512, 512]`` straight from the grouped
    ``gsbn,rgn->gsbr`` product and hands ``u`` straight to the signal-noise
    combination and ``gsbr,rgv->bgsv``: no copy or select makes the one or
    reads the other, no 640-wide padded query is made and no select runs
    over a ``[512, 80, 512]`` result (the kernel writes an inactive row's
    zeros itself)."""
    cfg, e, _, compiled = _motif_step(one_chip)
    text = compiled.as_text()
    B, H = e["batch_size"], cfg.num_attention_heads
    assert "bf16[%d,%d,640]" % (B, H) not in text
    assert not [ln for name, ln in _instructions(text).items()
                if "select" in name and ("[%d,%d,512]" % (B, H) in ln
                                         or "[%d,%d,512]" % (H, B) in ln)]
    found = _kernel_neighbours(text, kernel, operand)
    assert len(found) == (cfg.full_layers if "paged" in kernel
                          else cfg.window_layers)
    for made, read in found:
        assert not _relayout(made), made[:160]
        assert read and not [ln for ln in read if _relayout(ln)], read
