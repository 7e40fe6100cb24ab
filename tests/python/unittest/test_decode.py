"""Stateful decode serving (mxnet_tpu/serving/decode.py + kvcache.py +
the frontdoor/client streaming wire, ISSUE 18).

The contracts under test:
  * paged allocator invariants — block conservation, no aliasing, the
    null block never allocated, overflow is TYPED and mutates nothing;
  * continuous-batched decode is BIT-IDENTICAL per sequence to solo
    decode while sequences join and leave mid-run (the fixed-shape
    step + null-block masking make partial batches inert);
  * exactly two programs per (model, prefill-bucket) family — one
    prefill per bucket + one step — AOT-warmed and FLAT under traffic;
  * cache pressure sheds typed (`CacheOverflow`, a DeadlineExceeded):
    a never-fit prompt rejects immediately, a sequence outgrowing the
    pool mid-generation sheds with its partial output intact;
  * streaming over the safe wire — incremental token frames, terminal
    status frame, and exactly-once RESUME by id across a killed
    connection (no token lost, none duplicated), with the gateway
    accounting invariant `submitted == served + shed + failed` holding
    with streams in flight.
"""
import threading
import time

import numpy as np
import pytest

from mxnet_tpu.serving import (ModelServer, ServingFrontDoor, ServingClient,
                               DeadlineExceeded, DecodeEngine, PagedKVCache,
                               CacheOverflow, NULL_BLOCK)
from mxnet_tpu.models.tiny_lm import TinyLMDecodeModel


def _lm():
    return TinyLMDecodeModel().engine_kwargs()


def _tf_walk_sizes(B, mb, bs):
    """The GPT-2 family's walk sizes: the shared rule at its constants."""
    from mxnet_tpu.kernels.paged_attention import walk_sizes
    from mxnet_tpu.models.transformer import _WALK_ROWS, _WALK_SPAN
    return walk_sizes(B, mb, bs, _WALK_ROWS, _WALK_SPAN)


def _tf_walk_plan(positions, tables, bs):
    from mxnet_tpu.kernels.paged_attention import walk_plan
    from mxnet_tpu.models.transformer import _WALK_ROWS, _WALK_SPAN
    return walk_plan(positions, tables, bs, _WALK_ROWS, _WALK_SPAN)


# ---------------------------------------------------------------------------
# paged allocator
# ---------------------------------------------------------------------------

class TestPagedAllocator:
    def test_churn_keeps_invariants(self):
        kv = PagedKVCache(num_blocks=9, block_size=4)
        rng = np.random.RandomState(7)
        live = []
        for i in range(200):
            kv.check()
            if live and rng.rand() < 0.4:
                kv.free(live.pop(rng.randint(len(live))))
            elif live and rng.rand() < 0.5:
                sid = live[rng.randint(len(live))]
                try:
                    kv.extend(sid, int(rng.randint(1, 5)))
                except CacheOverflow:
                    pass
            else:
                sid = "s%d" % i
                try:
                    kv.allocate(sid, int(rng.randint(1, 12)))
                    live.append(sid)
                except CacheOverflow:
                    pass
        for sid in live:
            kv.free(sid)
        kv.check()
        st = kv.stats()
        assert st["blocks_free"] == st["blocks_total"]
        assert st["allocs"] == st["frees"]
        assert st["blocks_high_water"] <= st["blocks_total"]

    def test_overflow_is_typed_and_mutates_nothing(self):
        kv = PagedKVCache(num_blocks=5, block_size=4)   # capacity 4 blocks
        kv.allocate("a", 12)                            # 3 blocks
        free_before = kv.free_blocks
        with pytest.raises(CacheOverflow) as exc:
            kv.allocate("b", 8)                         # needs 2, 1 free
        assert isinstance(exc.value, DeadlineExceeded)  # typed SHED
        assert kv.free_blocks == free_before
        assert "b" not in kv.sequences()
        # extend overflow: table and length unchanged
        table_before, len_before = kv.table("a"), kv.length("a")
        with pytest.raises(CacheOverflow):
            kv.extend("a", 16)
        assert kv.table("a") == table_before
        assert kv.length("a") == len_before
        assert kv.stats()["alloc_failures"] == 2
        kv.check()

    def test_null_block_never_handed_out(self):
        kv = PagedKVCache(num_blocks=4, block_size=2)
        kv.allocate("a", 6)                             # the whole pool
        assert NULL_BLOCK not in kv.table("a")
        assert kv.free_blocks == 0
        kv.check()

    def test_hbm_bounded_by_live_tokens(self):
        """The watermark counters prove occupancy tracks LIVE tokens,
        not max_length x batch."""
        kv = PagedKVCache(num_blocks=65, block_size=4)
        for i in range(4):
            kv.allocate("s%d" % i, 4)                   # 1 block each
        assert kv.live_blocks == 4                      # not 4 x max_len
        for i in range(4):
            kv.free("s%d" % i)
        assert kv.live_blocks == 0
        assert kv.stats()["blocks_high_water"] == 4


# ---------------------------------------------------------------------------
# decode engine: parity, programs, shedding
# ---------------------------------------------------------------------------

def _engine(**kw):
    kw.setdefault("name", "t%d" % (id(kw) % 100000))
    kw.setdefault("num_blocks", 64)
    kw.setdefault("batch_size", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    return DecodeEngine(**_lm(), **kw)


class _Slowly:
    """A step builder that takes its time, so that a deadline can pass in
    mid-generation."""

    def __init__(self, builder, seconds):
        self._builder, self._seconds = builder, seconds

    def __call__(self, *args):
        time.sleep(self._seconds)
        return self._builder(*args)

    def __getattr__(self, name):
        return getattr(self._builder, name)


class TestDecodeEngine:
    def test_continuous_matches_solo_with_join_leave(self):
        """The acceptance bit: per-sequence output under continuous
        batching (sequences joining and leaving mid-run, different
        lengths) is identical to decoding each prompt alone."""
        prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [8, 9, 7, 9, 3, 2],
                   [2, 7, 1, 8, 2, 8], [1], [4, 4, 4, 4], [6, 2, 6]]
        budgets = [6, 9, 4, 12, 7, 10, 5, 8]
        solo_eng = _engine(name="solo")
        solo = [solo_eng.generate(p, max_new_tokens=m)
                for p, m in zip(prompts, budgets)]
        solo_eng.stop()

        cont = _engine(name="cont", batch_size=3)   # < len(prompts): forced
        #                                             join/leave churn
        streams = []
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            streams.append(cont.submit(p, max_new_tokens=m))
            if i % 3 == 2:
                time.sleep(0.02)        # stagger arrivals mid-run
        outs = [s.result_wait(60.0) for s in streams]
        assert outs == solo, "continuous batching changed decode output"
        st = cont.stats()
        assert st["submitted"] == st["served"] == len(prompts)
        assert st["kv"]["blocks_live"] == 0     # everything retired
        cont.stop()

    def test_exactly_two_programs_per_family(self):
        eng = _engine(name="progs")
        assert eng.program_counts() == (2, 1)   # one per bucket + one step
        # traffic through BOTH buckets + partial batches must not compile
        for p in ([1, 2], [1] * 12, [7, 7, 7], [9] * 16):
            eng.generate(p, max_new_tokens=4)
        assert eng.program_counts() == (2, 1)
        st = eng.stats()
        assert st["programs"] == {"prefill": 2, "step": 1}
        eng.stop()

    def test_never_fit_prompt_sheds_typed(self):
        eng = _engine(name="oom1", num_blocks=3, prefill_buckets=(16,),
                      max_seq_len=24)     # capacity: 2 blocks = 32 tokens? no:
        #                                   2 blocks x 16 block_size... use
        #                                   explicit block_size below instead
        eng.stop()
        eng = _engine(name="oom2", num_blocks=3, block_size=4,
                      prefill_buckets=(16,), max_seq_len=24)
        # capacity 2 blocks = 8 tokens; a 10-token prompt can NEVER fit
        stream = eng.submit([1] * 10, max_new_tokens=4)
        with pytest.raises(CacheOverflow):
            stream.result_wait(30.0)
        assert stream.outcome == "shed"
        st = eng.stats()
        assert st["shed"] == 1 and st["cache_oom"] == 1
        assert st["submitted"] == st["served"] + st["shed"] + st["failed"]
        eng.stop()

    def test_mid_generation_overflow_sheds_typed_with_partial_output(self):
        eng = _engine(name="oom3", num_blocks=3, block_size=4,
                      prefill_buckets=(8,), max_seq_len=24, batch_size=2)
        # capacity 8 tokens: a 5-token prompt admits (2 blocks), but
        # growth past position 8 needs a third block -> overflow MID-run
        stream = eng.submit([5, 4, 3, 2, 1], max_new_tokens=10)
        with pytest.raises(CacheOverflow):
            stream.result_wait(30.0)
        assert stream.outcome == "shed"
        assert len(stream.tokens) == 4      # prefill + 3 steps landed
        assert eng.stats()["kv"]["blocks_live"] == 0    # blocks reclaimed
        eng.stop()

    def test_deadline_shed_before_admission_is_typed(self):
        eng = _engine(name="dl")
        stream = eng.submit([1, 2, 3], max_new_tokens=4, deadline_ms=0.01)
        with pytest.raises(DeadlineExceeded):
            stream.result_wait(30.0)
        assert stream.outcome == "shed"
        eng.stop()

    def test_eos_retires_early(self):
        eng = _engine(name="eos")
        free_run = eng.generate([2, 7, 1], max_new_tokens=10)
        eos = free_run[2]       # a token the free run emits mid-sequence
        eng.stop()
        eng = _engine(name="eos2", eos_id=eos)
        out = eng.generate([2, 7, 1], max_new_tokens=10)
        # identical prefix up to the FIRST eos occurrence, emitted THEN
        # retired (the free run may hit it before index 2)
        assert out == free_run[:free_run.index(eos) + 1]
        eng.stop()

    @pytest.mark.parametrize("case", ["budget", "eos", "deadline", "tight",
                                      "lost"])
    def test_one_step_ahead_serves_what_the_synchronous_loop_serves(
            self, case):
        """The loop dispatches step N + 1 behind step N before N's ids are
        read (`steps_ahead` counts them) and every sequence still gets the
        tokens of the synchronous iteration: rows that end on their budget
        are left out of the step behind, a row that ends on an EOS only
        the host can see is stepped once for nothing and passed over, an
        eviction waits for the tokens before it, a pool without a block a
        row runs synchronously, and a lost read-back fails both steps'
        rows and nothing else."""
        prompts = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [8, 9, 7, 9, 3, 2],
                   [2, 7, 1, 8, 2, 8], [1]]
        budgets = [14, 25, 9, 30, 17, 22]
        kw = dict(batch_size=3)
        if case == "eos":
            kw["eos_id"] = _engine(name="a0").generate(
                prompts[1], max_new_tokens=25)[6]
        if case == "tight":
            # 9 usable blocks of 4: the three longest cannot all grow
            kw.update(num_blocks=10, block_size=4, max_seq_len=40)

        def drive(name, ahead):
            eng = _engine(name=name, autostart=False, **kw)
            if not ahead:
                eng._follows = lambda step: False
            if case == "lost" and ahead:
                get, lost = eng._device_get, []

                def device_get(x):      # the first read-back with a step
                    if eng._ahead is not None and not lost:  # behind it
                        lost.append(1)
                        raise OSError("lost")
                    return get(x)
                eng._device_get = device_get
            streams = [eng.submit(p, max_new_tokens=m,
                                  deadline_ms=150.0 if case == "deadline"
                                  and i == 1 else None)
                       for i, (p, m) in enumerate(zip(prompts, budgets))]
            if case == "deadline":
                # the deadline passes in mid-generation
                eng._step_b = _Slowly(eng._step_b, 0.01)
            eng.start()
            outs = []
            for s in streams:
                try:
                    outs.append(s.result_wait(60.0))
                except (DeadlineExceeded, RuntimeError) as e:
                    outs.append((type(e), list(s.tokens)))
            st = eng.stats()
            eng.stop()
            assert st["kv"]["blocks_live"] == 0
            assert st["submitted"] == st["served"] + st["shed"] + st["failed"]
            return outs, st

        sync, st0 = drive("s" + case, ahead=False)
        outs, st = drive("a" + case, ahead=True)
        assert st0["steps_ahead"] == 0 and st["steps_ahead"] > 0
        if case == "deadline":
            kind, partial = outs[1]
            assert kind is DeadlineExceeded and sync[1][0] is kind
            # it kept what had landed: a prefix of the unhurried answer
            whole = _engine(name="w").generate(prompts[1], max_new_tokens=25)
            assert 1 <= len(partial) < 25 and partial == whole[:len(partial)]
            outs[1] = sync[1] = None
        if case == "lost":
            failed = [o for o in outs if isinstance(o, tuple)]
            assert failed and all(k is RuntimeError for k, _ in failed)
            assert st["failed"] == len(failed) <= 3 and st["served"] >= 3
            for o, ref, p in zip(outs, sync, prompts):
                toks = o[1] if isinstance(o, tuple) else o
                assert toks == ref[:len(toks)]
            return
        assert outs == sync
        if case == "tight":
            assert st["cache_oom"] == st0["cache_oom"] > 0
        if case in ("budget", "tight"):     # the others end on the clock,
            # or step an ended row once for nothing
            assert st["steps"] == st0["steps"]
            assert st["tokens"] == st0["tokens"]

    def test_invalid_prompts_raise_synchronously(self):
        eng = _engine(name="bad")
        with pytest.raises(ValueError):
            eng.submit([])
        with pytest.raises(ValueError):
            eng.submit([1] * 40)        # over the largest bucket (16)
        assert eng.stats()["submitted"] == 0    # nothing counted
        eng.stop()

    def test_chunked_prefill_bit_identical_and_flat_programs(self):
        """Chunked prefill (ISSUE 19): same outputs as whole-prompt
        prefill, programs stay len(buckets)+1, long prompts beyond the
        largest bucket become admissible, chunks are counted."""
        prompts = [[3, 1, 4], [1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9],
                   [5] * 16, [2, 7]]
        whole = _engine(name="ckw")
        ref = [whole.generate(p, max_new_tokens=6) for p in prompts]
        whole.stop()
        eng = _engine(name="ckc", prefill_chunk=8)
        out = [eng.generate(p, max_new_tokens=6) for p in prompts]
        assert out == ref, "chunked prefill changed decode output"
        # beyond the largest bucket (16) — only admissible chunked
        long_out = eng.generate(list(range(1, 31)), max_new_tokens=4)
        assert len(long_out) == 4
        assert eng.program_counts() == (2, 1)
        st = eng.stats()
        assert st["prefill_chunks"] > 0
        assert st["submitted"] == st["served"]
        eng.stop()


# ---------------------------------------------------------------------------
# transformer decode body (models/transformer.py, ISSUE 19)
# ---------------------------------------------------------------------------

def _tf_model(flash="off", num_layers=2, max_len=64):
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              TransformerDecodeModel)
    cfg = TransformerConfig(vocab_size=64, num_layers=num_layers,
                            num_heads=4, d_model=32, max_len=max_len,
                            block_k=16)
    return TransformerDecodeModel(cfg, flash=flash)


def _tf_engine(model, name, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("batch_size", 3)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    return DecodeEngine(**model.engine_kwargs(), name=name, **kw)


def _gather_out_sizes(jaxpr, operand_ndim=None):
    """Element count of every `gather` equation's output, sub-jaxprs
    (pjit, loops, custom calls) included; with ``operand_ndim`` only of
    the gathers that read an operand of that rank (4: a page pool)."""
    import jax
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and operand_ndim in (
                None, eqn.invars[0].aval.ndim):
            sizes.extend(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes.extend(_gather_out_sizes(sub, operand_ndim))
    return sizes


class TestTransformerDecode:
    PROMPTS = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3], [8, 9, 7, 9, 3, 2],
               [2, 7, 1, 8, 2, 8], [1], [4, 4, 4, 4]]
    BUDGETS = [6, 9, 4, 12, 7, 10, 5]

    def test_continuous_matches_solo_multilayer(self):
        """The acceptance bit on the REAL model: multi-layer multi-head
        decode under continuous batching (batch 3 < 7 prompts forces
        join/leave churn) is bit-identical per sequence to solo."""
        model = _tf_model()
        solo_eng = _tf_engine(model, "tfsolo")
        solo = [solo_eng.generate(p, max_new_tokens=m)
                for p, m in zip(self.PROMPTS, self.BUDGETS)]
        solo_eng.stop()
        cont = _tf_engine(model, "tfcont")
        streams = []
        for i, (p, m) in enumerate(zip(self.PROMPTS, self.BUDGETS)):
            streams.append(cont.submit(p, max_new_tokens=m))
            if i % 3 == 2:
                time.sleep(0.02)
        outs = [s.result_wait(120.0) for s in streams]
        assert outs == solo, "continuous transformer decode != solo"
        assert cont.program_counts() == (2, 1)
        assert cont.stats()["kv"]["blocks_live"] == 0
        cont.stop()

    def test_chunked_prefill_matches_whole_prompt(self):
        model = _tf_model()
        whole = _tf_engine(model, "tfw")
        ref = [whole.generate(p, max_new_tokens=m)
               for p, m in zip(self.PROMPTS, self.BUDGETS)]
        whole.stop()
        chunked = _tf_engine(model, "tfc", prefill_chunk=8)
        out = [chunked.generate(p, max_new_tokens=m)
               for p, m in zip(self.PROMPTS, self.BUDGETS)]
        assert out == ref, "chunked transformer prefill changed output"
        # long prompt beyond the largest bucket decodes chunked
        long_out = chunked.generate([7] * 30, max_new_tokens=4)
        assert len(long_out) == 4
        assert chunked.program_counts() == (2, 1)
        chunked.stop()

    def test_flash_interpret_tier_matches_lax_tier_tokens(self):
        """The flash-kernel prefill path (interpret tier off-TPU, the
        _flash_fwd_offs_kernel block-table variant reading paged KV)
        produces the same token stream as the lax tier."""
        lax = _tf_model(flash="off")
        assert lax.flash_engaged is False
        flash = _tf_model(flash="interpret")
        assert flash.flash_engaged is True
        prompts, budgets = self.PROMPTS[:4], self.BUDGETS[:4]
        le = _tf_engine(lax, "tflax")
        ref = [le.generate(p, max_new_tokens=m)
               for p, m in zip(prompts, budgets)]
        le.stop()
        fe = _tf_engine(flash, "tfflash")
        out = [fe.generate(p, max_new_tokens=m)
               for p, m in zip(prompts, budgets)]
        fe.stop()
        assert out == ref, "flash-tier transformer decode diverged"

    @pytest.mark.parametrize("num_layers", [1, 2, 4])
    def test_no_gather_carries_a_layer_axis(self, num_layers):
        """Each layer reads only its own pages: traced on the engine's
        own argument shapes, no gather of a prefill bucket is larger than
        ONE layer's view of the block table, and no gather of the step is
        larger than one PIECE of the walk (rows per block x span)."""
        import jax
        from mxnet_tpu.models.transformer import _WALK_ROWS
        model = _tf_model(num_layers=num_layers)
        eng = _tf_engine(model, "tfg%d" % num_layers, warmup=False,
                         autostart=False)
        sd = jax.ShapeDtypeStruct
        b, mb, bs = eng.batch_size, eng._mb, eng._kv.block_size
        d_model = model.cfg.d_model
        i32 = np.int32

        def gathers(fn, *args, **kw):
            return _gather_out_sizes(jax.make_jaxpr(fn)(
                eng._params, eng._cache_spec, *args).jaxpr, **kw)

        for bucket in eng.prefill_buckets:
            sizes = gathers(model.prefill_fn, sd((bucket,), i32),
                            sd((), i32), sd((), i32), sd((mb,), i32),
                            sd((), i32))
            one_layer = mb * bs * d_model
            assert sizes.count(one_layer) == 2 * num_layers     # K and V
            assert max(sizes) == one_layer
        # the step at the engine's shapes, and at shapes that split both
        # ways (several row blocks, several pieces a table)
        for rows, blocks in ((b, mb), (4 * _WALK_ROWS, 1024 // bs)):
            rb, cb = _tf_walk_sizes(rows, blocks, bs)
            if rows > b:
                assert rb < rows and cb < blocks
            # the reads of the pools: K and V of a layer each gather one
            # piece inside the walk's loop body, which the jaxpr holds
            # once a layer
            sizes = gathers(model.step_fn, sd((rows,), i32),
                            sd((rows,), i32), sd((rows, blocks), i32),
                            sd((rows,), np.bool_), operand_ndim=4)
            assert sizes == [rb * cb * bs * d_model] * (2 * num_layers)

    @staticmethod
    def _walk_case(rows, seed=0, blocks=64, bs=16):
        """A step's arguments at ``rows`` rows over tables of ``blocks``
        blocks: every row its own blocks of a random pool, lengths from 1
        to the table's end, two rows inactive."""
        import jax.numpy as jnp
        model = _tf_model(max_len=blocks * bs)
        cfg = model.cfg
        rng = np.random.RandomState(seed)
        pool = (cfg.num_layers, 1 + rows * blocks, bs, cfg.d_model)
        cache = {k: jnp.asarray(rng.standard_normal(pool), jnp.float32)
                 for k in ("k", "v")}
        tables = 1 + np.arange(rows * blocks, dtype=np.int32) \
            .reshape(rows, blocks)
        positions = rng.randint(0, blocks * bs, rows).astype(np.int32)
        positions[:3] = [0, blocks * bs - 1, 255]
        active = np.ones(rows, np.bool_)
        active[[5, rows - 2]] = False
        positions[~active] = 0
        tables[~active] = 0
        tokens = rng.randint(0, cfg.vocab_size, rows).astype(np.int32)
        return model, cache, tokens, positions, tables, active

    @staticmethod
    def _whole_table_step(params, cfg, cache, token_ids, positions, tables,
                          active):
        """The step as it was before the walk: every row attends over its
        table's full ``mb * bs`` positions through a head-split view. Kept
        here as the plain formula the walk is held against."""
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.models.transformer import _layer_norm
        k_pages, v_pages = cache["k"], cache["v"]
        B, mb = tables.shape
        bs = k_pages.shape[2]
        H, Dh = cfg.num_heads, cfg.d_model // cfg.num_heads
        T = mb * bs
        x = params["embed"][token_ids] + params["pos_embed"][positions]
        blk = jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)
        blk = jnp.where(active, blk[:, 0], 0)
        slot = positions % bs
        tpos = jnp.arange(T, dtype=jnp.int32)[None, None, :]
        ctxs = []
        for l in range(cfg.num_layers):
            lp = {k: v[l] for k, v in params["layers"].items()}
            h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"])
            q = (h @ lp["wq"]).reshape(B, H, Dh)
            k_pages = k_pages.at[l, blk, slot].set(h @ lp["wk"])
            v_pages = v_pages.at[l, blk, slot].set(h @ lp["wv"])
            ks = k_pages[l, tables].reshape(B, T, H, Dh)
            vs = v_pages[l, tables].reshape(B, T, H, Dh)
            scores = jnp.einsum("bhd,bthd->bht", q, ks) / np.sqrt(Dh)
            scores = jnp.where(tpos <= positions[:, None, None], scores,
                               -1e30)
            w = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bht,bthd->bhd", w, vs).reshape(B, cfg.d_model)
            ctxs.append(ctx)
            x = x + ctx @ lp["wo"]
            h = _layer_norm(x, lp["ln2_scale"], lp["ln2_bias"])
            x = x + (jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
                     + lp["b2"])
        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        return x @ params["embed"].T, ctxs

    @pytest.mark.parametrize("row_blocks", [1, 2, 4])
    def test_live_walk_matches_whole_table_attention(self, row_blocks):
        """The walk over live positions against the whole-table formula
        it replaced: greedy ids equal, a layer's contexts to float32
        tolerance; several row blocks, several pieces a table, lengths
        from 1 to the table's end, inactive rows included."""
        import jax
        from mxnet_tpu.models import transformer as tf
        rows = tf._WALK_ROWS * row_blocks
        model, cache, *args = self._walk_case(rows)
        rb, cb = _tf_walk_sizes(rows, args[2].shape[1], 16)
        assert rows // rb == row_blocks and args[2].shape[1] // cb >= 2
        ids, new_cache, _ = jax.jit(model.step_fn)(model.params, cache,
                                                   *args)
        logits, ctxs = jax.jit(
            lambda *a: self._whole_table_step(a[0], model.cfg, *a[1:]))(
            model.params, cache, *args)
        logits = np.asarray(logits)
        best = logits.argmax(-1)
        ids = np.asarray(ids)
        # greedy ids equal (a tie within float32 rounding aside: none of
        # these rows has one)
        top2 = np.sort(logits, axis=-1)[:, -2:]
        assert (top2[:, 1] - top2[:, 0]).min() > 1e-4
        assert (ids == best).all()
        # layer contexts: the walk's own attention against the formula's
        plan = _tf_walk_plan(args[1], args[2], 16)
        lp0 = {k: v[0] for k, v in model.params["layers"].items()}
        x = model.params["embed"][args[0]] + model.params["pos_embed"][args[1]]
        q = tf._layer_norm(x, lp0["ln1_scale"], lp0["ln1_bias"]) @ lp0["wq"]
        got = tf._live_attention(q, new_cache["k"], new_cache["v"], 0, plan,
                                 model.cfg.num_heads)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ctxs[0]),
                                   rtol=2e-5, atol=2e-6)

    def test_row_result_does_not_depend_on_its_block_mates(self):
        """A row's context is bit-identical whichever rows share its
        block and however far they make the block walk: shuffle and
        redraw the OTHER rows' lengths, keep three rows as they are."""
        import jax
        from mxnet_tpu.models import transformer as tf
        rows = 2 * tf._WALK_ROWS
        model, cache, tokens, positions, tables, active = \
            self._walk_case(rows)
        keep = [3, 7, rows - 1]
        q = np.random.RandomState(1).standard_normal(
            (rows, model.cfg.d_model)).astype(np.float32)

        @jax.jit
        def attend(q, positions, tables):
            plan = _tf_walk_plan(positions, tables, 16)
            return tf._live_attention(q, cache["k"], cache["v"], 1, plan,
                                      model.cfg.num_heads)

        def contexts_of(positions, tables, slots):
            q2 = np.zeros_like(q)
            q2[slots] = q[keep]
            return np.asarray(attend(q2, positions, tables))[slots]

        ref = contexts_of(positions, tables, keep)
        assert np.abs(ref).min() > 0
        rng = np.random.RandomState(5)
        for trial in range(3):
            perm = rng.permutation(rows)
            pos2 = rng.randint(0, 1024, rows).astype(np.int32)
            tab2 = tables[perm].copy()      # other rows: other tables
            slots = [int(np.where(perm == k)[0][0]) for k in keep]
            pos2[slots] = positions[keep]
            if trial == 2:                  # short mates: a short walk
                others = np.setdiff1d(np.arange(rows), slots)
                pos2[others] = rng.randint(0, 16, len(others))
            got = contexts_of(pos2, tab2, slots)
            assert (got == ref).all(), "trial %d" % trial

    def test_walk_counters_reach_stats(self):
        """``kv_live_tokens`` is the sum of ``positions + 1`` over active
        rows; ``kv_walked_tokens`` lies between it and rows x table; both
        ride the step's read-back into ``stats()["model"]``."""
        import jax
        from mxnet_tpu.models import transformer as tf
        rows = 2 * tf._WALK_ROWS
        model, cache, tokens, positions, tables, active = \
            self._walk_case(rows)
        _, _, aux = jax.jit(model.step_fn)(model.params, cache, tokens,
                                           positions, tables, active)
        live, walked = int(aux["kv_live_tokens"]), int(aux["kv_walked_tokens"])
        assert live == int((positions[active] + 1).sum())
        assert live <= walked <= rows * tables.shape[1] * 16
        assert walked < rows * tables.shape[1] * 16     # the walk stops short
        model = _tf_model()
        eng = _tf_engine(model, "tfaux")
        outs = [eng.generate(p, max_new_tokens=m)
                for p, m in zip(self.PROMPTS[:3], self.BUDGETS[:3])]
        st = eng.stats()
        eng.stop()
        # a step of a sequence with c cached tokens attends over c + 1
        want = sum(sum(len(p) + i + 1 for i in range(len(o) - 1))
                   for p, o in zip(self.PROMPTS, outs))
        assert st["model"]["kv_live_tokens"] == want
        assert st["model"]["kv_walked_tokens"] >= want
        assert st["model"]["kv_walked_tokens"] == st["steps"] * 3 * 64

    def test_no_head_split_buffer_at_positions_size(self):
        """From the step's jaxpr at the cell's shapes (64 rows, 64 x 16
        table, 12 heads of 64): no intermediate whose two minor
        dimensions are ``(num_heads, head_dim)`` holds more than one
        piece's positions, and none holds rows x table of them: that
        pair is what the (8,128) tile pads from 768 lanes to 2,048."""
        import jax
        from mxnet_tpu.models.transformer import (
            TransformerConfig, TransformerDecodeModel)
        cfg = TransformerConfig(vocab_size=128, num_layers=2, num_heads=12,
                                d_model=768, d_ff=64, max_len=1024)
        sd = jax.ShapeDtypeStruct
        params = jax.eval_shape(
            lambda: TransformerDecodeModel(cfg, flash="off").params)
        model = TransformerDecodeModel(cfg, params=params, flash="off")
        B, mb, bs = 64, 64, 16
        rb, cb = _tf_walk_sizes(B, mb, bs)
        assert rb < B and cb < mb
        i32 = np.int32
        jaxpr = jax.make_jaxpr(model.step_fn)(
            params, model.cache_spec(1729, bs, B), sd((B,), i32),
            sd((B,), i32), sd((B, mb), i32), sd((B,), np.bool_)).jaxpr

        def shapes(jaxpr):
            for eqn in jaxpr.eqns:
                for v in eqn.outvars:
                    yield tuple(v.aval.shape)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from shapes(sub)

        seen = set(shapes(jaxpr))
        assert (rb, cb * bs, 768) in seen           # a piece, rows in lanes
        for s in (s for s in seen if s[-2:] == (12, 64)):
            assert int(np.prod(s[:-2])) <= rb * cb * bs, s
        assert not [s for s in seen
                    if int(np.prod(s)) >= B * mb * bs * 768], \
            "a whole-table buffer survives"

    def test_pool_is_layer_major_and_layers_write_their_own_pages(self):
        """The model states the pools (``cache_spec``) and the engine
        builds them: (num_layers, num_blocks, block_size, d_model). After serving,
        layer l's K/V rows sit under pages[l] at the positions the
        sequences' tables name, the same slots for every layer, and
        nowhere else (block 0 takes the padding writes)."""
        model = _tf_model(num_layers=3)
        eng = _tf_engine(model, "tflm", num_blocks=32, block_size=4)
        assert sorted(eng._cache) == ["k", "v"]
        assert eng._cache["k"].shape == eng._cache["v"].shape \
            == (3, 32, 4, 32)
        assert eng.stats()["kv"]["pool_bytes"] == 2 * 3 * 32 * 4 * 32 * 4
        outs = [eng.generate(p, max_new_tokens=m)
                for p, m in (([3, 1, 4], 2), ([1, 5, 9, 2, 6, 5, 3, 5, 8], 4))]
        assert [len(o) for o in outs] == [2, 4]
        eng.stop()
        # the longer sequence holds positions 0..11 (the last token
        # emitted is never written), the shorter one 0..3, in a block
        # that the allocator may have handed out again
        for pages in (np.asarray(eng._cache["k"]),
                      np.asarray(eng._cache["v"])):
            written = np.abs(pages[:, 1:]).sum(axis=-1) > 0    # (L, N-1, bs)
            assert 12 <= written[0].sum() <= 16
            for l in range(3):
                assert (written[l] == written[0]).all()
            # layers hold different values at the same slots
            assert not np.allclose(pages[0], pages[1])
            assert not np.allclose(pages[1], pages[2])

    def test_mesh_placed_pages_do_not_change_tokens(self):
        """tp-sharded KV pages (kvcache.page_sharding): placement is a
        layout choice, not a numeric one."""
        from mxnet_tpu.parallel import get_mesh
        from mxnet_tpu.serving.kvcache import page_sharding
        model = _tf_model()
        mesh = get_mesh(dp=2, tp=4)
        ps = page_sharding(mesh, (2, 64, 16, 32), "tp")
        assert ps.spec[-1] == "tp"      # d_model (heads) sharded
        # indivisible trailing dim stays replicated
        assert page_sharding(mesh, (2, 64, 16, 30), "tp").spec == \
            type(ps.spec)()
        plain = _tf_engine(model, "tfpl")
        ref = [plain.generate(p, max_new_tokens=6) for p in self.PROMPTS[:3]]
        plain.stop()
        placed = _tf_engine(model, "tfms", mesh=mesh)
        out = [placed.generate(p, max_new_tokens=6)
               for p in self.PROMPTS[:3]]
        assert out == ref
        placed.stop()


# ---------------------------------------------------------------------------
# streaming over the wire
# ---------------------------------------------------------------------------

def _gateway(**engine_kw):
    engine_kw.setdefault("num_blocks", 64)
    engine_kw.setdefault("batch_size", 4)
    engine_kw.setdefault("max_seq_len", 64)
    engine_kw.setdefault("prefill_buckets", (16,))
    eng = DecodeEngine(**_lm(), name="lm", **engine_kw)
    srv = ModelServer()
    srv.register_decode("lm", eng)
    fd = ServingFrontDoor(srv, port=0).start()
    return eng, srv, fd


class TestWireStreaming:
    def test_stream_matches_engine_and_frames_are_ordered(self):
        eng, srv, fd = _gateway()
        cl = ServingClient("127.0.0.1", fd.port)
        try:
            seen = []
            st = cl.decode_async([3, 1, 4, 1, 5], model="lm",
                                 max_new_tokens=8,
                                 on_token=lambda s, n, t: seen.append((n, t)))
            out = st.result_wait(60.0)
            assert out == eng.generate([3, 1, 4, 1, 5], max_new_tokens=8)
            assert [n for n, _ in seen] == list(range(1, len(out) + 1))
            assert [t for _, t in seen] == out
            # iteration surface delivers the same thing
            assert list(cl.decode_async([2, 2], model="lm",
                                        max_new_tokens=5)) == \
                eng.generate([2, 2], max_new_tokens=5)
        finally:
            cl.close()
            fd.drain(timeout=10.0)
            srv.stop()

    def test_killed_connection_resumes_by_id_exactly_once(self):
        """The acceptance bit for streams: kill the transport mid-stream;
        the client resumes by id and the delivered seq_nos are exactly
        1..N — nothing lost, nothing replayed."""
        eng, srv, fd = _gateway()
        cl = ServingClient("127.0.0.1", fd.port)
        try:
            got, killed = [], []

            def on_tok(s, n, t):
                got.append((n, t))
                if n == 3 and not killed:
                    killed.append(1)
                    cl.fail_over()      # break the transport mid-stream
            st = cl.decode_async([5, 5, 5], model="lm", max_new_tokens=12,
                                 on_token=on_tok)
            out = st.result_wait(60.0)
            assert killed, "stream finished before the kill point"
            assert out == eng.generate([5, 5, 5], max_new_tokens=12)
            assert [n for n, _ in got] == list(range(1, len(out) + 1))
            assert cl.stats["stream_resumes"] >= 1
            fstats = fd.stats()
            assert fstats["stream_resumes"] >= 1
            assert fstats["submitted"] == (fstats["served"] + fstats["shed"]
                                           + fstats["failed"])
        finally:
            cl.close()
            fd.drain(timeout=10.0)
            srv.stop()

    def test_accounting_invariant_with_streams_and_failures(self):
        eng, srv, fd = _gateway()
        cl = ServingClient("127.0.0.1", fd.port)
        try:
            oks = [cl.decode_async([i + 1, 2], model="lm", max_new_tokens=4)
                   for i in range(5)]
            with pytest.raises(Exception, match="unknown decode model"):
                cl.decode([1], model="nope", timeout=30.0)
            with pytest.raises(DeadlineExceeded):
                # typed shed either client-side (budget gone before the
                # send) or at the gateway (wire consumed it) — both are
                # the same DeadlineExceeded contract
                cl.decode([1, 2], model="lm", deadline_ms=0.01, timeout=30.0)
            for st in oks:
                st.result_wait(60.0)
            s = fd.stats()
            assert s["submitted"] == s["served"] + s["shed"] + s["failed"]
            assert s["served"] >= 5 and s["failed"] >= 1
            assert s["stream_frames"] >= sum(len(st.tokens) for st in oks)
        finally:
            cl.close()
            fd.drain(timeout=10.0)
            srv.stop()

    def test_pinning_routes_same_sequence_to_same_replica(self):
        """Stateful dispatch: the same pin lands on the same replica
        (its KV state lives there); hedging never sees decode."""
        a = DecodeEngine(**_lm(), name="lm", num_blocks=32,
                         batch_size=2, max_seq_len=32, prefill_buckets=(8,))
        b = DecodeEngine(**_lm(), name="lm", num_blocks=32,
                         batch_size=2, max_seq_len=32, prefill_buckets=(8,))
        srv = ModelServer()
        srv.register_decode("lm", a)
        srv.register_decode("lm", b)
        try:
            for _ in range(3):
                srv.submit_decode("lm", [1, 2], max_new_tokens=2,
                                  pin="seq-42").result_wait(30.0)
            counts = (a.stats()["submitted"], b.stats()["submitted"])
            assert sorted(counts) == [0, 3]     # all on ONE replica
        finally:
            srv.stop()
