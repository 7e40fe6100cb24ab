"""Driver: a served decoder behind ``DecodeEngine``.

Traffic ``kind: backlog`` submits every request at the opening of the window
(offline generation: the queue never empties) and reads tokens per second;
``kind: open_loop`` sends arrivals at a fixed rate from one thread (gaps as
the mix's ``arrivals`` says, ``harness/traffic.py``), times every request from
its DUE time, and drains afterwards.

The weights are made by the benchmark's reference from the seed and handed to
the program; the check replays a sample of finished requests, the longest
among them, through the reference's full forward pass.
"""
import functools
import time

import numpy as np

from harness import flops, loadgen
from harness.context import Compared, key_from_seed
from harness.traffic import make_requests, percentile


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.eng = None

    # ------------------------------------------------------------------
    def _build(self):
        from mxnet_tpu.models.transformer import (TransformerConfig,
                                                  TransformerDecodeModel)
        from mxnet_tpu.serving.decode import DecodeEngine
        ctx, cfg = self.ctx, self.ctx.config
        self.params = ctx.reference.init_params(cfg, key_from_seed(ctx.seed))
        tcfg = TransformerConfig(
            vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
            num_heads=cfg["n_head"], d_model=cfg["n_embd"],
            d_ff=cfg["n_inner"], max_len=cfg["n_positions"])
        tier = "interpret" if ctx.rehearse else "auto"
        model = TransformerDecodeModel(tcfg, params=self.params, flash=tier)
        if not model.flash_engaged:
            raise RuntimeError("decode prefill resolved to the lax tier")
        e = dict(ctx.traffic["engine"])
        e["prefill_buckets"] = tuple(e["prefill_buckets"])
        self.eng = DecodeEngine(**model.engine_kwargs(), name="bench",
                                default_deadline_ms=None, **e)
        self.engine_cfg = e

    def _submit(self, req):
        t0 = self.t_open

        def on_token(stream, seq_no, token, _r=req):
            _r.token_s.append(time.monotonic() - t0)

        req.stream = self.eng.submit(req.prompt, max_new_tokens=req.max_new,
                                     on_token=on_token)

    def _warm(self):
        """Run every program of the family once before the clock: each
        prefill bucket, the chunked path and the step."""
        rng = np.random.default_rng(0)
        vocab = self.ctx.config["vocab_size"]
        lens = [max(1, b - 3) for b in self.engine_cfg["prefill_buckets"]]
        lens.append(self.engine_cfg["prefill_chunk"] + lens[0])
        streams = [self.eng.submit(rng.integers(0, vocab, n).astype(np.int32),
                                   max_new_tokens=4) for n in lens]
        for s in streams:
            s.result_wait(600.0)

    # ------------------------------------------------------------------
    def run(self):
        from mxnet_tpu import profiler
        ctx, t = self.ctx, self.ctx.traffic
        c0 = profiler.compile_counters()
        t0 = time.time()
        self._build()
        t1 = time.time()
        self._warm()
        ctx.log("setup", since_start_s=t0 - ctx.t_process_start,
                build_s=t1 - t0, warm_s=time.time() - t1)
        reqs = make_requests(t, ctx.config["vocab_size"], ctx.seed,
                             ctx.seconds)
        self.requests = reqs
        eng = self.eng
        s0 = eng.stats()
        self.compiles_open = profiler.compile_counters()
        self.t_open_wall = time.time()
        self.t_open = t_open = time.monotonic()
        ctx.tracer.open_window(t_open)
        t_end = t_open + ctx.seconds
        tick = ctx.tracer.tick
        if t["kind"] == "backlog":
            loadgen.send_all(reqs, self._submit, t_open, ctx.span, tick)
            loadgen.wait_until(t_end, tick)
            ctx.tracer.stop()
            s1 = eng.stats()
            t_close = time.monotonic()
            due = reqs
        else:
            loadgen.send_all(reqs, self._submit, t_open, ctx.span, tick,
                             stop_at=t_end)
            loadgen.wait_until(t_end, tick)
            ctx.tracer.stop()
            s1 = eng.stats()
            due = [r for r in reqs if r.sent_s is not None]
            with ctx.span("bench.drain"):
                loadgen.wait_until(
                    t_end + float(t.get("drain_s", 30.0)),
                    done=lambda: all(r.stream.done() for r in due))
            t_close = time.monotonic()
        c1 = profiler.compile_counters()
        kv = eng.stats()["kv"]
        eng.stop()
        return self._facts(due, s0, s1, kv, c0, c1, t_close - t_open)

    def _facts(self, due, s0, s1, kv, c0, c1, elapsed_s):
        ctx, t, cfg = self.ctx, self.ctx.traffic, self.ctx.config
        seconds = ctx.seconds
        finished = [r for r in due
                    if r.stream is not None and r.stream.outcome == "served"
                    and len(r.token_s) == len(r.stream.tokens)]
        in_window = [r for r in finished if r.token_s[-1] <= seconds]
        self.finished = in_window if t["kind"] == "backlog" else finished
        tokens = sum(1 for r in due for ts in r.token_s if ts <= seconds)
        d = {k: s1[k] - s0[k] for k in
             ("tokens", "steps", "prefills", "prefill_chunks", "served",
              "failed", "shed")}
        # model operations of the window: every prompt token prefilled and
        # every token stepped, attention over the context live at the time
        L, dm, ff, V = (cfg["n_layer"], cfg["n_embd"], cfg["n_inner"],
                        cfg["vocab_size"])
        model_flops = 0
        for r in due:
            n_tok = sum(1 for ts in r.token_s if ts <= seconds)
            if not n_tok:
                continue
            p = len(r.prompt)
            model_flops += sum(flops.decoder_flops_per_token(L, dm, ff, c + 1)
                               for c in range(p))
            model_flops += sum(
                flops.decoder_flops_per_token(L, dm, ff, p + i + 1)
                for i in range(n_tok - 1))
            model_flops += n_tok * flops.logits_flops(dm, V)
        facts = {
            "window_open_wall": self.t_open_wall,
            "window_s": seconds, "tokens": tokens,
            "model_flops": model_flops,
            "step_tokens": d["tokens"] - d["prefills"], "steps": d["steps"],
            "batch_size": self.engine_cfg["batch_size"],
            "prefills": d["prefills"], "prefill_chunks": d["prefill_chunks"],
            "kv_blocks_high_water": kv["blocks_high_water"],
            "kv_blocks_total": kv["blocks_total"],
            "compile_s_setup": (self.compiles_open["total"]["compile_ms"]
                                - c0["total"]["compile_ms"]) / 1e3,
            "compiles_in_window": (c1["total"]["compiles"]
                                   - self.compiles_open["total"]["compiles"]),
        }
        if t["kind"] == "backlog":
            facts["end_to_end"] = {"decode_tok_per_s": tokens / seconds}
            facts["attempted"] = len(in_window) + d["failed"] + d["shed"]
            facts["failed"] = d["failed"] + d["shed"]
            self.never = 0
        else:
            ttft = loadgen.ttft_ms(due)
            itl = loadgen.inter_token_ms(due)
            late = loadgen.lateness_ms(due)
            limit_ms = (seconds + float(t.get("drain_s", 30.0))) * 1e3
            p90 = percentile(ttft, 90)
            facts["end_to_end"] = {
                "ttft_p90_ms": min(p90, limit_ms) if ttft else limit_ms,
                "itl_p95_ms": percentile(itl, 95) if itl else limit_ms}
            facts["generator_late_p95_ms"] = percentile(late, 95)
            facts["ttft_p50_ms"] = percentile(ttft, 50)
            facts["attempted"] = len(due)
            self.never = len(due) - len(finished)
            facts["failed"] = self.never
            facts["waiting_at_close"] = s1["waiting"]
            facts["drained_s"] = elapsed_s - seconds
        ctx.log("window", kind=t["kind"], requests_due=len(due),
                finished=len(self.finished), tokens=tokens,
                engine=d, kv_high_water=kv["blocks_high_water"],
                waiting_at_close=s1["waiting"], active_at_close=s1["active"],
                persistent_cache_hits=c1["persistent_cache_hits"],
                end_to_end=facts["end_to_end"],
                **{k: v for k, v in facts.items()
                   if k in ("generator_late_p95_ms", "ttft_p50_ms",
                            "drained_s")})
        return facts

    def release(self):
        """Free the engine (pages, programs); keep the finished requests'
        token ids on the host and the benchmark's own weights."""
        self.served = [(r.prompt, np.asarray(r.stream.tokens, np.int32))
                       for r in self.finished]
        for r in self.requests:
            r.stream = None
        self.eng = None

    # ------------------------------------------------------------------
    def sample(self):
        """Finished requests drawn from the seed, the longest among them."""
        n = int(self.ctx.traffic["check"]["sample_requests"])
        served = self.served
        if not served:
            return []
        rng = np.random.default_rng([self.ctx.seed, 7])
        longest = max(range(len(served)),
                      key=lambda i: len(served[i][0]) + len(served[i][1]))
        rest = [i for i in range(len(served)) if i != longest]
        pick = [longest] + list(rng.permutation(rest)[:n - 1])
        return [served[i] for i in pick]

    def check(self, control_in_place=False):
        """Served tokens against the reference's full forward pass, the
        sample in blocks of ``check.block_requests`` (one compiled shape).
        ``control_in_place`` (proof runs): the lower-precision forward's own
        tokens stand where the served ones did."""
        ctx, cfg = self.ctx, self.ctx.config
        import jax
        picked = self.sample()
        out = [Compared("never_answered", self.never, 0)]
        if not picked:
            out.append(Compared("served_gap_ratio", float("inf"),
                                ctx.limit("served_gap_ratio")))
            return out
        n = int(ctx.traffic["check"]["block_requests"])
        S = cfg["n_positions"]
        K = int(ctx.traffic["output_len"]["max"])
        fn = jax.jit(functools.partial(ctx.reference.served_gaps, cfg,
                                       yardstick_dtype=cfg["control"]))
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
            gs, gl = fn(self.params, tokens, pos, served, valid)
            served_g.append(np.asarray(gs)[valid])
            low_g.append(np.asarray(gl)[valid])
        low_g = np.concatenate(low_g)
        served_g = low_g if control_in_place else np.concatenate(served_g)
        yard = float(np.mean(low_g * low_g))
        self.reported = {
            "requests": len(picked), "tokens": int(served_g.size),
            "tokens_off_best": int((served_g > 0).sum()),
            "gap_max": float(served_g.max()),
            "gap_mean_sq": float(np.mean(served_g * served_g)),
            "yardstick_mean_sq": yard,
            "longest": int(max(len(p) + len(t) for p, t in picked))}
        ctx.log("check", **self.reported)
        # mean squared gap of the served tokens over that of the lower
        # precision's own tokens on the same weights and prompts: the widest
        # gap, and any gap not set against this yardstick, swing from seed
        # to seed by more than the control differs (PERF.md section 2)
        out.append(Compared("served_gap_ratio",
                            self.reported["gap_mean_sq"] / max(yard, 1e-30),
                            ctx.limit("served_gap_ratio")))
        return out
