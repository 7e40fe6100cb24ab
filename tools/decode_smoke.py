#!/usr/bin/env python
"""CI stateful-decode smoke (`ci/run.py decode_smoke` stage, ISSUE 18).

Fast, non-slow gate over the decode serving tier:
  * two REAL client OS processes stream autoregressive decodes over the
    TCP wire; every streamed output is BIT-IDENTICAL to solo
    `DecodeEngine.generate` on the same prompt (continuous batching may
    not change a single token);
  * one client breaks its transport mid-stream and resumes by sequence
    id: the delivered `seq_no`s are exactly 1..N — zero tokens lost,
    zero duplicated — across the killed connection;
  * cache pressure sheds TYPED across the socket: a never-fit prompt is
    refused up front and a sequence that outgrows the pool
    mid-generation sheds with its partial output intact, both arriving
    as `DeadlineExceeded` client-side;
  * the program family stays at exactly len(prefill_buckets) + 1
    compiled programs after all traffic (the steady-state loop never
    recompiles), the paged allocator drains back to zero live blocks,
    and `submitted == served + shed + failed` holds gateway-side with
    the whole stream counted as ONE request;
  * the REAL transformer decode body (ISSUE 19) on the 8-device mesh:
    the flash kernel tier must ENGAGE (interpret off-TPU — asserted,
    never a silent lax fallback), chunked prefill must admit a
    past-the-bucket prompt, and the flash-tier engine with tp-sharded
    KV pages must stream tokens identical to the lax-tier solo engine,
    at the same flat program family.

Prints one JSON summary line; non-zero exit on any violated contract.
The companion lint half of the stage (tpulint over mxnet_tpu/serving)
runs as a second command in ci/run.py.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the transformer-decode section shards KV pages over a dp×tp mesh:
# force the 8-device host platform unless the caller already did
# (ci/run.py passes cpu_mesh_env(8); standalone runs get it here)
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

from mxnet_tpu.models.tiny_lm import TinyLMDecodeModel  # noqa: E402
from mxnet_tpu.serving import (ModelServer, ServingFrontDoor,  # noqa: E402
                               DecodeEngine)

# Client subprocess body: a REAL ServingClient in a REAL second OS
# process streaming decodes — the acceptance criteria are cross-process
# bit-parity and exactly-once delivery across a killed connection.
_CLIENT = r'''
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, %(root)r)
from mxnet_tpu.serving import ServingClient, DeadlineExceeded
port, seed = int(sys.argv[1]), int(sys.argv[2])
cli = ServingClient("127.0.0.1", port)
out = {"outs": [], "seqs_ok": True, "kill_fired": False}

# --- streamed decodes on the healthy engine; seed 1 breaks its
# transport mid-stream on the third prompt ------------------------------
prompts = [[seed, i + 1, (seed * 7 + i) %% 11 + 1] for i in range(5)]
for i, prompt in enumerate(prompts):
    got = []
    def on_tok(st, n, t, _i=i, _got=got):
        _got.append((n, t))
        if seed == 1 and _i == 2 and n == 3 and not out["kill_fired"]:
            out["kill_fired"] = True
            cli.fail_over()      # break every transport, mid-stream
    st = cli.decode_async(prompt, model="lm", max_new_tokens=8 + i,
                          on_token=on_tok)
    toks = st.result_wait(60.0)
    out["outs"].append(toks)
    if [t for _, t in sorted(got)] != toks or \
            sorted(n for n, _ in got) != list(range(1, len(toks) + 1)):
        out["seqs_ok"] = False
        out["bad_seq"] = {"prompt": prompt, "got": sorted(got),
                          "toks": toks}
out["resumes"] = cli.stats.get("stream_resumes", 0)

# --- typed shed: never-fit prompt on the starved engine ----------------
try:
    cli.decode(list(range(1, 11)), model="tiny", max_new_tokens=4,
               timeout=60.0)
    out["neverfit_typed"] = False
except DeadlineExceeded as e:
    out["neverfit_typed"] = "never fit" in str(e)
except Exception as e:
    out["neverfit_typed"] = "%%s: %%s" %% (type(e).__name__, str(e)[:200])

# --- typed shed mid-generation, partial output retained ----------------
st = cli.decode_async([seed, 2, 3, 4, 5], model="tiny", max_new_tokens=10)
try:
    st.result_wait(60.0)
    out["midgen_typed"] = False
except DeadlineExceeded:
    out["midgen_typed"] = True
except Exception as e:
    out["midgen_typed"] = "%%s: %%s" %% (type(e).__name__, str(e)[:200])
out["midgen_partial"] = len(st.tokens)
cli.close()
print(json.dumps(out))
'''


def main():
    lm = TinyLMDecodeModel().engine_kwargs()
    # healthy engine: pool comfortably covers the traffic
    eng = DecodeEngine(**lm, name="lm", num_blocks=64, batch_size=4,
                       max_seq_len=96, prefill_buckets=(16,))
    # starved engine: 2 usable blocks x 4 tokens = 8-token capacity, so
    # a 10-token prompt can never fit and a 5-token prompt overflows
    # mid-generation — both must shed typed across the wire
    tiny = DecodeEngine(**lm, name="tiny", block_size=4, num_blocks=3,
                        batch_size=2, max_seq_len=64, prefill_buckets=(16,))
    srv = ModelServer()
    srv.register_decode("lm", eng)
    srv.register_decode("tiny", tiny)
    fd = ServingFrontDoor(srv, port=0).start()

    script = _CLIENT % {"root": ROOT}
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(fd.port), str(seed)],
        stdout=subprocess.PIPE, text=True) for seed in (1, 2)]
    reports = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, out
        reports.append(json.loads(out.strip().splitlines()[-1]))

    # --- bit-parity vs solo decode, exactly-once seq_nos ---------------
    for seed, rep in zip((1, 2), reports):
        assert rep["seqs_ok"], rep
        prompts = [[seed, i + 1, (seed * 7 + i) % 11 + 1] for i in range(5)]
        for i, (prompt, toks) in enumerate(zip(prompts, rep["outs"])):
            solo = eng.generate(prompt, max_new_tokens=8 + i)
            assert toks == solo, \
                "continuous batching diverged from solo decode: " \
                "%r -> %r != %r" % (prompt, toks, solo)
        assert rep["neverfit_typed"] is True, rep
        assert rep["midgen_typed"] is True, rep
        assert rep["midgen_partial"] >= 1, rep
    assert reports[0]["kill_fired"], reports[0]
    assert reports[0]["resumes"] >= 1, reports[0]

    # --- program family flat, allocator drained, accounting exact ------
    st_lm, st_tiny = eng.stats(), tiny.stats()
    assert st_lm["programs"] == {"prefill": 1, "step": 1}, st_lm
    assert st_tiny["programs"] == {"prefill": 1, "step": 1}, st_tiny
    assert st_lm["kv"]["blocks_live"] == 0, st_lm["kv"]
    assert st_tiny["kv"]["blocks_live"] == 0, st_tiny["kv"]
    assert st_tiny["cache_oom"] >= 4, st_tiny      # 2 never-fit + 2 midgen
    fs = fd.stats()
    assert fs["submitted"] == fs["served"] + fs["shed"] + fs["failed"], fs
    assert fs["stream_resumes"] >= 1, fs
    n_toks = sum(len(t) for rep in reports for t in rep["outs"])
    assert fs["stream_frames"] >= n_toks, fs

    # --- transformer decode on the 8-device mesh (ISSUE 19) ------------
    # the real multi-layer multi-head body: kernel tier must ENGAGE
    # (interpret off-TPU), chunked prefill must admit a past-the-bucket
    # prompt, and the flash-tier engine with tp-sharded KV pages must
    # stream the SAME tokens as the lax-tier solo engine.
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              TransformerDecodeModel)
    from mxnet_tpu.parallel import get_mesh
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            d_model=32, max_len=64, block_k=16)
    flash_model = TransformerDecodeModel(cfg, seed=0, flash="interpret")
    assert flash_model.flash_engaged, \
        "kernel tier did not engage (interpret off-TPU) — transformer " \
        "prefill would silently run the lax tier"
    lax_model = TransformerDecodeModel(cfg, params=flash_model.params,
                                       flash="off")
    assert not lax_model.flash_engaged
    mesh = get_mesh(dp=2, tp=4)
    tf_eng = DecodeEngine(name="tf", num_blocks=64, batch_size=3,
                          max_seq_len=64, prefill_buckets=(8, 16),
                          prefill_chunk=8, mesh=mesh,
                          **flash_model.engine_kwargs())
    ref_eng = DecodeEngine(name="tf_ref", num_blocks=64, batch_size=3,
                           max_seq_len=64, prefill_buckets=(8, 16),
                           prefill_chunk=8, **lax_model.engine_kwargs())
    tf_prompts = [[(7 * i + j) % 63 + 1 for j in range(3 + 2 * i)]
                  for i in range(5)]
    tf_prompts.append([5] * 20)       # past the largest bucket: only the
    #                                   chunked path can admit it
    sts = [tf_eng.submit(p, max_new_tokens=6) for p in tf_prompts]
    tf_outs = [s.result_wait(180.0) for s in sts]
    for p, got in zip(tf_prompts, tf_outs):
        want = ref_eng.generate(p, max_new_tokens=6, timeout=180.0)
        assert got == want, \
            "flash-tier mesh engine diverged from lax solo: %r -> %r " \
            "!= %r" % (p, got, want)
    st_tf = tf_eng.stats()
    assert st_tf["programs"] == {"prefill": 2, "step": 1}, st_tf
    assert st_tf["prefill_chunks"] > 0, st_tf
    assert st_tf["kv"]["blocks_live"] == 0, st_tf["kv"]
    tf_eng.stop()
    ref_eng.stop()

    # --- the latent-attention expert family (ISSUE 28) ------------------
    # models/moe_mla.py through the SAME engine and cache seam: one latent
    # pool (kept whole on the mesh: a latent row has no head axis), the
    # flash tier engaged for prefill, the absorbed step, four of eight
    # experts held; the mesh-placed flash engine must stream the tokens of
    # the lax-tier solo engine, and the model's counters must add up.
    from mxnet_tpu.models.moe_mla import MoEMLAConfig, MoEMLADecodeModel
    mcfg = MoEMLAConfig(
        hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=160, moe_intermediate_size=48, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=2.5,
        rms_norm_eps=1e-5, rope_theta=25.6e6, vocab_size=64,
        experts_held=(2, 4), initializer_range=0.2, block_k=16,
        step_row_block=2, step_col_blocks=2)
    moe_flash = MoEMLADecodeModel(mcfg, seed=0, dtype="float32",
                                  flash="interpret", mesh=mesh)
    assert moe_flash.flash_engaged
    moe_lax = MoEMLADecodeModel(mcfg, params=moe_flash.params, flash="off")
    moe_eng = DecodeEngine(name="moe", num_blocks=64, batch_size=3,
                           max_seq_len=64, prefill_buckets=(8, 16),
                           prefill_chunk=8, mesh=mesh,
                           **moe_flash.engine_kwargs())
    moe_ref = DecodeEngine(name="moe_ref", num_blocks=64, batch_size=3,
                           max_seq_len=64, prefill_buckets=(8, 16),
                           prefill_chunk=8, **moe_lax.engine_kwargs())
    sts = [moe_eng.submit(p, max_new_tokens=6) for p in tf_prompts]
    moe_outs = [s.result_wait(180.0) for s in sts]
    for p, got in zip(tf_prompts, moe_outs):
        want = moe_ref.generate(p, max_new_tokens=6, timeout=180.0)
        assert got == want, \
            "moe_mla flash-tier mesh engine diverged from lax solo: %r -> " \
            "%r != %r" % (p, got, want)
    st_moe = moe_eng.stats()
    assert st_moe["programs"] == {"prefill": 2, "step": 1}, st_moe
    assert st_moe["kv"]["blocks_live"] == 0, st_moe["kv"]
    mm = st_moe["model"]
    assert mm["moe_layer_steps"] == st_moe["steps"] * mcfg.num_expert_layers
    assert 0 < mm["moe_assignments"] <= (
        (st_moe["tokens"] - st_moe["prefills"]) * mcfg.num_expert_layers
        * mcfg.num_experts_per_tok), mm
    assert st_moe["kv"]["pool_bytes"] == 3 * 64 * 16 * 128 * 4, st_moe["kv"]
    moe_eng.stop()
    moe_ref.stop()

    summary = {
        "clients": reports,
        "transformer": {"flash_engaged": True,
                        "prefill_chunks": st_tf["prefill_chunks"],
                        "programs": st_tf["programs"],
                        "mesh": {"dp": 2, "tp": 4},
                        "sequences": len(tf_prompts)},
        "moe_mla": {"flash_engaged": True, "programs": st_moe["programs"],
                    "prefill_chunks": st_moe["prefill_chunks"],
                    "model": mm, "pool_bytes": st_moe["kv"]["pool_bytes"],
                    "experts_held": list(mcfg.experts_held)},
        "frontdoor": {k: v for k, v in fs.items() if v},
        "lm": {"counters": {k: v for k, v in st_lm.items()
                            if isinstance(v, int) and v},
               "kv": st_lm["kv"], "programs": st_lm["programs"]},
        "tiny": {"cache_oom": st_tiny["cache_oom"],
                 "kv": st_tiny["kv"]},
    }
    print(json.dumps(summary), flush=True)
    assert fd.drain(timeout=30.0)
    srv.stop()
    print("decode_smoke OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
