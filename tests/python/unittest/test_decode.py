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
                               CacheOverflow, NULL_BLOCK, tiny_lm_params)


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
    return DecodeEngine(tiny_lm_params(), **kw)


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

def _tf_model(flash="off", num_layers=2):
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              TransformerDecodeModel)
    cfg = TransformerConfig(vocab_size=64, num_layers=num_layers,
                            num_heads=4, d_model=32, max_len=64, block_k=16)
    return TransformerDecodeModel(cfg, flash=flash)


def _tf_engine(model, name, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("batch_size", 3)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    return DecodeEngine(**model.engine_kwargs(), name=name, **kw)


def _gather_out_sizes(jaxpr):
    """Element count of every `gather` equation's output, sub-jaxprs
    (pjit, custom calls) included."""
    import jax
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            sizes.extend(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes.extend(_gather_out_sizes(sub))
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
        own argument shapes, no gather of the step or of a prefill
        bucket is larger than ONE layer's view of the block table."""
        import jax
        model = _tf_model(num_layers=num_layers)
        eng = _tf_engine(model, "tfg%d" % num_layers, warmup=False,
                         autostart=False)
        sd = jax.ShapeDtypeStruct
        b, mb, bs = eng.batch_size, eng._mb, eng._kv.block_size
        i32 = np.int32
        # (program, its arguments after the cache, one layer's view)
        programs = [(model.step_fn, (sd((b,), i32), sd((b,), i32),
                                     sd((b, mb), i32), sd((b,), np.bool_)),
                     b * mb * bs * model.cfg.d_model)]
        programs += [(model.prefill_fn, (sd((bucket,), i32), sd((), i32),
                                         sd((), i32), sd((mb,), i32)),
                      mb * bs * model.cfg.d_model)
                     for bucket in eng.prefill_buckets]
        for fn, args, one_layer in programs:
            sizes = _gather_out_sizes(
                jax.make_jaxpr(fn)(eng._params, eng._cache_spec,
                                   *args).jaxpr)
            assert sizes.count(one_layer) == 2 * num_layers     # K and V
            assert max(sizes) == one_layer

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
    eng = DecodeEngine(tiny_lm_params(), name="lm", **engine_kw)
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
        a = DecodeEngine(tiny_lm_params(), name="lm", num_blocks=32,
                         batch_size=2, max_seq_len=32, prefill_buckets=(8,))
        b = DecodeEngine(tiny_lm_params(), name="lm", num_blocks=32,
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
