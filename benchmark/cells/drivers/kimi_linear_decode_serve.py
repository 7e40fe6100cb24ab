"""Driver: the Kimi-Linear decoder (gated delta-rule layers with per-slot
recurrent state beside latent-attention layers, experts) behind
``DecodeEngine``.

``drivers/decode_serve.py`` with four things of its own, as
``drivers/moe_decode_serve.py`` has them: the build
(``KimiLinearDecodeModel`` over the reference's bfloat16 weights, handed over
as they are), the operation and byte counts (``harness/flops_kimi_linear.py``;
routed experts and updated state rows by the program's own counters), the
facts the per-layer metrics read (the expert layer's and the latent cache's
under the names the other latent cell gives them, and the recurrent state's),
and the check's sizes (ONE full forward over the request's own length rounded
up to 1,024 positions, a request at a time, the reference called outside
``jit`` so that each layer is a program of its own). Everything else (the
window, the load, every other fact) is the existing driver's, loaded by name.
"""
import numpy as np

from harness import flops_kimi_linear as fk
from harness.context import Compared, key_from_seed

MODEL_COUNTERS = ("moe_assignments", "moe_busiest", "moe_experts_touched",
                  "moe_layer_steps", "kv_live_tokens", "kda_rows_updated",
                  "kda_layer_steps", "prefill_kda_chunks")
# the GPT-2 sizes the existing driver's own count reads: zero here, so that
# count comes out 0 and this driver's replaces it
NO_GPT2_COUNT = {"n_layer": 0, "n_embd": 0, "n_inner": 0}
CHECK_QUANTUM = 1024    # a checked request's forward is padded to this


def Driver(ctx):
    """The class is made per run: its base is found through the spec, as
    every other file of a cell is."""

    class KimiLinearDecodeDriver(
            ctx.spec.module("drivers", "decode_serve").Driver):
        def _build(self):
            # a program without this family stops here, before any weight
            from mxnet_tpu.models.kimi_linear import (KimiLinearConfig,
                                                      KimiLinearDecodeModel)
            from mxnet_tpu.serving.decode import DecodeEngine
            ctx, cfg = self.ctx, self.ctx.config
            self.params = ctx.reference.init_params(cfg,
                                                    key_from_seed(ctx.seed))
            tier = "interpret" if ctx.rehearse else "auto"
            model = KimiLinearDecodeModel(KimiLinearConfig.from_dict(cfg),
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
                    ops += fk.sequence_flops(cfg, len(r.prompt), n_tok - 1)
                    ops += n_tok * fk.head_flops(cfg)
            ops += fk.routed_flops(cfg, d.get("moe_assignments", 0)
                                   + d.get("prefill_moe_assignments", 0))
            facts["model_flops"] = ops
            facts["experts_held"] = int(cfg["experts_held"]["count"])
            facts["kv_pool_bytes"] = kv.get("pool_bytes")
            facts["kv_state_bytes"] = kv.get("state_bytes")
            for k in MODEL_COUNTERS:
                facts[k] = d.get(k)
            steps = facts["steps"]
            if steps and d.get("kda_layer_steps"):
                state_b = np.dtype(cfg.get("state_dtype",
                                           "float32")).itemsize
                rows = d["kda_rows_updated"] / steps
                facts["kda_kernel_bytes"] = fk.kda_kernel_bytes(
                    cfg, rows, state_b)
                facts["kda_step_bytes"] = fk.kda_step_bytes(
                    cfg, rows, state_b)
                facts["step_hbm_bytes"] = fk.step_hbm_bytes(
                    cfg, d.get("moe_experts_touched", 0) / steps,
                    d.get("kv_live_tokens", 0) / steps, rows,
                    state_bytes=state_b)
            # steps whose successor was queued before they were read back
            # (an engine without the counter logs None)
            ahead = None if "steps_ahead" not in s1 \
                else s1["steps_ahead"] - s0["steps_ahead"]
            ctx.log("model", counters=d, model_flops=ops, steps_ahead=ahead,
                    kv_pool_bytes=facts["kv_pool_bytes"],
                    kv_state_bytes=facts["kv_state_bytes"],
                    kda_step_bytes=facts.get("kda_step_bytes"),
                    step_hbm_bytes=facts.get("step_hbm_bytes"))
            return facts

        def check(self, control_in_place=False):
            """Served tokens against the reference's ONE full forward pass,
            as the existing drivers compare them, a request at a time
            (``check.block_requests`` 1): the forward runs over the
            request's own length rounded up to ``CHECK_QUANTUM`` positions
            (the recurrence costs a scan step a position: the engine's
            ``max_seq_len`` would double the check for nothing), the
            reference outside ``jit``, a layer a program."""
            ctx, cfg = self.ctx, self.ctx.config
            picked = self.sample()
            out = [Compared("never_answered", self.never, 0)]
            if not picked:
                out.append(Compared("served_gap_ratio", float("inf"),
                                    ctx.limit("served_gap_ratio")))
                return out
            n = int(ctx.traffic["check"]["block_requests"])
            K = int(ctx.traffic["output_len"]["max"])
            served_g, low_g = [], []
            for b in range(0, len(picked), n):
                block = picked[b:b + n]
                longest = max(len(p) + len(t) for p, t in block)
                S = -(-longest // CHECK_QUANTUM) * CHECK_QUANTUM
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

    return KimiLinearDecodeDriver(ctx)
