"""Driver: the Motif-3 decoder (four hyper-connected residual streams,
grouped differential attention over latent rows, window layers beside full
ones, PolyNorm experts) behind ``DecodeEngine``.

``drivers/decode_serve.py`` with four things of its own, as
``drivers/moe_decode_serve.py`` has them: the build (``MotifDecodeModel``
over the reference's bfloat16 weights, handed over as they are), the
operation and byte counts (``harness/flops_motif.py``; routed experts and
attended latent rows by the program's own counters), the facts the
per-layer metrics read (the expert layer's and the latent cache's under the
names the other latent cells give them, and the attention kernels'), and the
check's sizes (a full forward over ``engine.max_seq_len`` positions, a
request at a time, the reference called outside ``jit`` so that each layer
is a program of its own and fits beside the 7.9 GB of weights). Everything
else (the window, the load, every other fact) is the existing driver's,
loaded by name.
"""
import numpy as np

from harness import flops_motif as fm
from harness.context import Compared, key_from_seed

MODEL_COUNTERS = ("moe_assignments", "moe_busiest", "moe_experts_touched",
                  "moe_layer_steps", "kv_live_tokens", "gdla_full_rows",
                  "gdla_window_rows", "gdla_context_positions")
# the GPT-2 sizes the existing driver's own count reads: zero here, so that
# count comes out 0 and this driver's replaces it
NO_GPT2_COUNT = {"n_layer": 0, "n_embd": 0, "n_inner": 0}


def Driver(ctx):
    """The class is made per run: its base is found through the spec, as
    every other file of a cell is."""

    class MotifDecodeDriver(ctx.spec.module("drivers", "decode_serve").Driver):
        def _build(self):
            # a program without this family stops here, before any weight
            from mxnet_tpu.models.motif import MotifConfig, MotifDecodeModel
            from mxnet_tpu.serving.decode import DecodeEngine
            ctx, cfg = self.ctx, self.ctx.config
            self.params = ctx.reference.init_params(cfg,
                                                    key_from_seed(ctx.seed))
            tier = "interpret" if ctx.rehearse else "auto"
            model = MotifDecodeModel(MotifConfig.from_dict(cfg),
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
                    ops += fm.sequence_flops(cfg, len(r.prompt), n_tok - 1)
                    ops += n_tok * fm.head_flops(cfg)
            ops += fm.routed_flops(cfg, d.get("moe_assignments", 0)
                                   + d.get("prefill_moe_assignments", 0))
            facts["model_flops"] = ops
            facts["experts_held"] = int(cfg["experts_held"]["count"])
            facts["kv_pool_bytes"] = kv.get("pool_bytes")
            facts["kv_state_bytes"] = kv.get("state_bytes")
            for k in MODEL_COUNTERS:
                facts[k] = d.get(k)
            if d.get("gdla_context_positions"):
                facts["gdla_attended_rows"] = d["gdla_full_rows"] \
                    + d["gdla_window_rows"]
            steps = facts["steps"]
            if steps and d.get("moe_layer_steps"):
                full = d["gdla_full_rows"] / steps
                window = d["gdla_window_rows"] / steps
                touched = d["moe_experts_touched"] / steps
                facts["gdla_attn_bytes"] = fm.attn_kernel_bytes(cfg, full,
                                                                window)
                facts["moe_expert_bytes"] = fm.expert_step_bytes(cfg,
                                                                 touched)
                facts["step_hbm_bytes"] = fm.step_hbm_bytes(
                    cfg, touched, full, window, facts["step_tokens"] / steps)
            # steps whose successor was queued before they were read back
            # (an engine without the counter logs None)
            ahead = None if "steps_ahead" not in s1 \
                else s1["steps_ahead"] - s0["steps_ahead"]
            ctx.log("model", counters=d, model_flops=ops, steps_ahead=ahead,
                    kv_pool_bytes=facts["kv_pool_bytes"],
                    kv_state_bytes=facts["kv_state_bytes"],
                    gdla_attn_bytes=facts.get("gdla_attn_bytes"),
                    moe_expert_bytes=facts.get("moe_expert_bytes"),
                    step_hbm_bytes=facts.get("step_hbm_bytes"))
            return facts

        def check(self, control_in_place=False):
            """Served tokens against the reference's full forward pass, as
            the existing drivers compare them: ``S`` is the engine's
            ``max_seq_len`` (one compiled shape a layer kind) and a block is
            ``check.block_requests`` requests (the reference runs a layer at
            a time and a query block at a time, outside ``jit``)."""
            ctx, cfg = self.ctx, self.ctx.config
            picked = self.sample()
            out = [Compared("never_answered", self.never, 0)]
            if not picked:
                out.append(Compared("served_gap_ratio", float("inf"),
                                    ctx.limit("served_gap_ratio")))
                return out
            n = int(ctx.traffic["check"]["block_requests"])
            S = int(ctx.traffic["engine"]["max_seq_len"])
            K = int(ctx.traffic["output_len"]["max"])
            served_g, low_g = [], []
            for b in range(0, len(picked), n):
                tokens = np.zeros((n, S), np.int32)
                pos = np.zeros((n, K), np.int32)
                served = np.zeros((n, K), np.int32)
                valid = np.zeros((n, K), bool)
                for i, (prompt, toks) in enumerate(picked[b:b + n]):
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

    return MotifDecodeDriver(ctx)
