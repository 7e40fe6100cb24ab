"""Driver: the EvaByte decoder (exact attention inside a window beside chunk
summaries of everything before it, in one page pool whose pages go back
while the sequence lives) behind ``DecodeEngine``.

``drivers/decode_serve.py`` with four things of its own, as
``drivers/kimi_linear_decode_serve.py`` has them: the build
(``EvaByteDecodeModel`` over the reference's bfloat16 weights, handed over as
they are), the operation and byte counts (``harness/flops_evabyte.py``;
attended cache rows by the program's own counters), the facts the per-layer
metrics read (what the cache forgot, what the allocator took back), and the
check's sizes (ONE full forward over the request's own length rounded up to
half a window, a request at a time, the reference called outside ``jit`` so
that each layer is a program of its own). Everything else (the window, the
load, every other fact) is the existing driver's, loaded by name.
"""
import numpy as np

from harness import flops_evabyte as fe
from harness.context import Compared, key_from_seed

MODEL_COUNTERS = ("eva_window_rows", "eva_summary_rows",
                  "eva_context_positions", "eva_chunks_pooled",
                  "eva_windows_closed", "kv_walked_tokens")
# the GPT-2 sizes the existing driver's own count reads: zero here, so that
# count comes out 0 and this driver's replaces it
NO_GPT2_COUNT = {"n_layer": 0, "n_embd": 0, "n_inner": 0}


def Driver(ctx):
    """The class is made per run: its base is found through the spec, as
    every other file of a cell is."""

    class EvaByteDecodeDriver(
            ctx.spec.module("drivers", "decode_serve").Driver):
        def _build(self):
            # a program without this family stops here, before any weight
            from mxnet_tpu.models.evabyte import (EvaByteConfig,
                                                  EvaByteDecodeModel)
            from mxnet_tpu.serving.decode import DecodeEngine
            ctx, cfg = self.ctx, self.ctx.config
            self.params = ctx.reference.init_params(cfg,
                                                    key_from_seed(ctx.seed))
            tier = "interpret" if ctx.rehearse else "auto"
            model = EvaByteDecodeModel(EvaByteConfig.from_dict(cfg),
                                       params=self.params, flash=tier)
            if not model.flash_engaged:
                raise RuntimeError("the kernels resolved to the lax tier")
            e = dict(ctx.traffic["engine"])
            e["prefill_buckets"] = tuple(e["prefill_buckets"])
            self.eng = DecodeEngine(**model.engine_kwargs(), name="bench",
                                    default_deadline_ms=None, **e)
            self.engine_cfg = e

        def _facts(self, due, s0, s1, kv, c0, c1, elapsed_s):
            ctx, cfg = self.ctx, self.ctx.config
            ctx.config = dict(cfg, **NO_GPT2_COUNT)
            try:
                facts = super()._facts(due, s0, s1, kv, c0, c1, elapsed_s)
            finally:
                ctx.config = cfg
            m0, m1 = s0.get("model", {}), s1.get("model", {})
            d = {k: v - m0.get(k, 0) for k, v in m1.items()}
            seconds = ctx.seconds
            ops = 0
            for r in due:
                n_tok = sum(1 for ts in r.token_s if ts <= seconds)
                if n_tok:
                    ops += fe.sequence_flops(cfg, len(r.prompt), n_tok - 1)
                    ops += n_tok * fe.head_flops(cfg)
            facts["model_flops"] = ops
            facts["kv_pool_bytes"] = kv.get("pool_bytes")
            for k in MODEL_COUNTERS:
                facts[k] = d.get(k)
            # what the allocator took from the pool and what it was handed
            # back by sequences still live, inside the window
            k0, k1 = s0["kv"], s1["kv"]
            facts["kv_blocks_allocated"] = k1["allocs"] - k0["allocs"]
            if "blocks_released_live" in k1:
                facts["kv_blocks_released_live"] = (
                    k1["blocks_released_live"] - k0["blocks_released_live"])
            steps = facts["steps"]
            if steps and d.get("eva_context_positions"):
                rows = d["eva_window_rows"] + d["eva_summary_rows"]
                facts["eva_attended_rows"] = rows
                facts["eva_attn_bytes"] = fe.attn_kernel_bytes(
                    cfg, rows / steps)
                facts["step_hbm_bytes"] = fe.step_hbm_bytes(cfg,
                                                            rows / steps)
            ahead = None if "steps_ahead" not in s1 \
                else s1["steps_ahead"] - s0["steps_ahead"]
            ctx.log("model", counters=d, model_flops=ops, steps_ahead=ahead,
                    kv_pool_bytes=facts["kv_pool_bytes"],
                    kv_blocks_allocated=facts["kv_blocks_allocated"],
                    kv_blocks_released_live=facts.get(
                        "kv_blocks_released_live"),
                    eva_attn_bytes=facts.get("eva_attn_bytes"),
                    step_hbm_bytes=facts.get("step_hbm_bytes"))
            return facts

        def check(self, control_in_place=False):
            """Served bytes against the reference's ONE full forward pass,
            as the existing drivers compare them, a request at a time
            (``check.block_requests`` 1): the forward runs over the
            request's own length rounded up to half a window, the reference
            outside ``jit``, a layer a program."""
            ctx, cfg = self.ctx, self.ctx.config
            picked = self.sample()
            out = [Compared("never_answered", self.never, 0)]
            if not picked:
                out.append(Compared("served_gap_ratio", float("inf"),
                                    ctx.limit("served_gap_ratio")))
                return out
            n = int(ctx.traffic["check"]["block_requests"])
            K = int(ctx.traffic["output_len"]["max"])
            quantum = cfg["window_size"] // 2
            served_g, low_g = [], []
            for b in range(0, len(picked), n):
                block = picked[b:b + n]
                longest = max(len(p) + len(t) for p, t in block)
                S = -(-longest // quantum) * quantum
                tokens = np.zeros((n, S), np.int32)
                pos = np.zeros((n, K), np.int32)
                served = np.zeros((n, K), np.int32)
                valid = np.zeros((n, K), bool)
                for i, (prompt, toks) in enumerate(block):
                    p, m = len(prompt), len(toks)
                    tokens[i, :p] = prompt
                    tokens[i, p:p + m - 1] = toks[:-1]
                    pos[i, :m] = p - 1 + np.arange(m)
                    served[i, :m] = toks
                    valid[i, :m] = True
                gs, gl = ctx.reference.served_gaps(
                    cfg, self.params, tokens, pos, served, valid,
                    yardstick_dtype=cfg["control"])
                served_g.append(np.asarray(gs)[valid])
                low_g.append(np.asarray(gl)[valid])
            low_g = np.concatenate(low_g)
            served_g = low_g if control_in_place \
                else np.concatenate(served_g)
            yard = float(np.mean(low_g * low_g))
            self.reported = {
                "requests": len(picked), "tokens": int(served_g.size),
                "tokens_off_best": int((served_g > 0).sum()),
                "gap_max": float(served_g.max()),
                "gap_mean_sq": float(np.mean(served_g * served_g)),
                "yardstick_mean_sq": yard,
                "longest": int(max(len(p) + len(t) for p, t in picked))}
            ctx.log("check", **self.reported)
            out.append(Compared(
                "served_gap_ratio",
                self.reported["gap_mean_sq"] / max(yard, 1e-30),
                ctx.limit("served_gap_ratio")))
            return out

    return EvaByteDecodeDriver(ctx)
