"""Stateful decode serving: continuous batching over a paged KV cache.

Everything the serving stack dispatched before this module was
stateless fixed-shape inference — one request, one program call, one
reply. Autoregressive decode breaks all three assumptions: a request is
a *sequence* that holds device state (its KV cache) across many program
calls, produces output incrementally, and finishes at a data-dependent
time. This module is the decode side of the stack (ISSUE 18):

- **Paged KV cache** (:mod:`.kvcache`): a sequence owns a block table
  and HBM scales with live tokens, not ``max_length x batch``.
- **Iteration-level continuous batching**: the decode loop generalizes
  the EDF batcher's formation pass. Between *every* step it retires
  finished sequences (EOS / max-new-tokens / deadline) and admits
  waiting ones (highest priority, then earliest deadline, then FIFO) —
  the batch stays full while sequences join and leave, and the
  deadline/shed contract is enforced per *token*, not per request
  (a sequence can be shed typed mid-generation, keeping the tokens it
  already produced). The loop runs ONE STEP AHEAD of the host: where the
  next step needs nothing that only the host knows, it is dispatched
  behind the step in flight before that step's ids are read back, and
  the host's share of a step (read-back, callbacks, growth, admission)
  runs while the device runs the next (``_decode_step``; ``stats()``
  ``["steps_ahead"]``).
- **Two-program family** through :class:`~..compile.builder.ProgramBuilder`
  (TPL108 seam): per model, one bucketed batch-1 *prefill* program per
  prompt-length bucket (site ``decode.prefill.<name>``) and exactly one
  fixed-shape batched *decode step* over the block table (site
  ``decode.step.<name>``). ``warmup()`` AOT-compiles the whole family,
  so ``program_count()`` is ``len(buckets)`` + 1 and stays there — the
  steady-state decode loop never compiles.

**The engine holds no model.** A family brings its cache and its two
bodies through the decode-model seam (:mod:`~..models.decode_model`):
``DecodeEngine(**model.engine_kwargs(), ...)``. Its clients are
:class:`~..models.transformer.TransformerDecodeModel` (GPT-2 style),
:class:`~..models.moe_mla.MoEMLADecodeModel` (latent attention, experts),
:class:`~..models.kimi_linear.KimiLinearDecodeModel` (per-slot state),
:class:`~..models.evabyte.EvaByteDecodeModel` (a cache that forgets)
and the tests' single-layer fixture beside them. The
device side of the page format (addressing, the null block, the step's
walk over the live positions, the prefill chunk's attention) is
:mod:`~..kernels.paged_attention`'s.

**The cache seam.** The model states its cache as a pytree of
``jax.ShapeDtypeStruct`` (``cache_spec(num_blocks, block_size, slots)``: any
shape and dtype a leaf). A leaf is one of two kinds. A *paged pool* (a plain
``ShapeDtypeStruct``) is indexed by the block tables `PagedKVCache` hands
out: keys and values, or latent rows, a row a cached token. A *per-slot pool*
(`~..models.decode_model.SlotPool`, leading axes ``[layers, slots, ...]``)
holds one row a decode SLOT: recurrent state that does not grow with the
sequence. The engine allocates every leaf, places it on the mesh, donates
it, describes it to ``aot_info`` and passes the cache WHOLE; it never looks
inside a leaf, and tells the two kinds apart only to account their bytes
(``stats()["kv"]``: ``pool_bytes`` and ``state_bytes``). The bodies:
``prefill_fn(params, cache, tokens, start, length, table, slot) ->
(next_id, cache, aux)``, ``slot`` the decode slot the prompt was admitted
to, and ``step_fn(params, cache, token_ids, positions, tables, active) ->
(next_ids, cache, aux)``, whose row ``i`` IS slot ``i``. A family without
per-slot state ignores ``slot``. The lifetime of a slot's state is the
model's: a piece with ``start == 0`` starts from zero state whatever the
slot held before (slots are re-used and never cleared by the engine), and a
step neither reads nor writes the state of a row whose ``active`` is false
(a vacant slot, or one in mid-prefill between two pieces of a chunked
prompt). ``aux`` is a dict of small integer arrays (may be empty) that comes
back in the same read-back as the ids and is summed into
``stats()["model"]``.

**Which table entries a sequence backs** is `PagedKVCache`'s to keep and
the family's to say: ``cache_pages(n)`` (optional; :mod:`.kvcache` has the
contract) names the entries a sequence of ``n`` positions needs. Without it
entry ``p // block_size`` holds position ``p`` for the sequence's whole
life. With it the table may have regions and holes, and a sequence hands
pages BACK as it grows (a window that closed): admission, growth, the
step-ahead test and the never-fit test all reckon with the same function;
a prompt is admitted when the most its prefill pieces need fits beside what
the next step of the rows already in the batch may take, and holds its own
from admission to its first step (`PagedKVCache.allocate`'s ``via``).

**Chunked prefill** (``prefill_chunk`` /
``MXNET_SERVING_DECODE_PREFILL_CHUNK``): a long prompt runs as
chunk-sized pieces through the same bucketed prefill programs (the
prefill seam carries a ``start`` offset), with one continuous-batching
step for the other active sequences between pieces — so a long prompt
no longer stalls the step loop, the program family stays
``len(buckets) + 1``, and outputs stay bit-identical to whole-prompt
prefill (masked lanes contribute exactly 0; attended positions already
hold final K/V bits).

Cache-pressure behavior: an allocation the pool cannot cover raises the
typed :class:`~.kvcache.CacheOverflow` (a ``DeadlineExceeded``
subclass) — a prompt that can never fit is shed immediately; a sequence
that outgrows the pool mid-generation is shed typed with its partial
output intact; a prompt that merely has to wait stays queued until
blocks free up or its deadline sheds it.

Observability: always-on counters via ``profiler.record_decode_event``
(tokens, steps, occupancy, cache OOMs) plus latency histograms
``decode.<name>.step`` (dispatch to read-back) /
``decode.<name>.ttft`` / ``decode.<name>.intertoken``; fault site ``decode.step`` fires before
every device dispatch (prefill and step) for chaos tests.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as _np

from .. import profiler as _prof
from ..base import get_env
from ..resilience import faults as _faults
from .batcher import DeadlineExceeded
from .kvcache import PagedKVCache, CacheOverflow

__all__ = ["DecodeEngine", "DecodeStream", "DEFAULT_DECODE_BUCKETS"]

#: Default prompt-length buckets for the prefill program family.
DEFAULT_DECODE_BUCKETS = (16, 64)

class DecodeStream:
    """Handle for one decode request: tokens appear incrementally, the
    terminal outcome resolves exactly once.

    ``tokens`` grows as the engine emits (generated token ``i`` has
    stream ``seq_no i+1`` — the numbering the wire frames carry).
    ``result_wait`` blocks for the terminal outcome and returns the full
    token list, raising the typed error on shed/failure (partial tokens
    stay readable on ``.tokens`` either way). Iterating the stream
    yields tokens as they are produced. ``on_token(stream, seq_no,
    token)`` / ``on_done(stream)`` callbacks run on the engine loop
    thread — keep them cheap (the front door only enqueues a frame)."""

    def __init__(self, rid, prompt, max_new_tokens, deadline, priority,
                 trace=None, on_token=None, on_done=None):
        self.rid = rid
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline        # absolute monotonic or None
        self.priority = priority
        self.trace = trace
        self.tokens = []
        self.error = None
        self.outcome = None             # "served" | "shed" | "failed"
        self._on_token = on_token
        self._on_done = on_done
        self._cond = threading.Condition()
        self._done_evt = threading.Event()
        self.submitted_t = time.monotonic()
        self.first_token_t = None
        self.last_token_t = None
        # positions with K/V on device; None while prefill is still in
        # flight — the step loop must not see a mid-prefill sequence
        self._cached = None
        # the dispatched step whose read-back yields this sequence's next
        # token, or None: the engine runs one step ahead of the host
        self._inflight = None

    def _emit(self, token):
        with self._cond:
            self.tokens.append(token)
            seq_no = len(self.tokens)
            self._cond.notify_all()
        if self._on_token is not None:
            self._on_token(self, seq_no, token)
        return seq_no

    def _resolve(self, error=None):
        with self._cond:
            if self._done_evt.is_set():
                return False
            self.error = error
            self.outcome = ("served" if error is None else
                            "shed" if isinstance(error, DeadlineExceeded)
                            else "failed")
            self._done_evt.set()
            self._cond.notify_all()
        if self._on_done is not None:
            self._on_done(self)
        return True

    def done(self):
        return self._done_evt.is_set()

    def result_wait(self, timeout=None):
        if not self._done_evt.wait(timeout):
            raise TimeoutError("decode stream %s still generating" % self.rid)
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    def __iter__(self):
        i = 0
        while True:
            with self._cond:
                while len(self.tokens) <= i and not self._done_evt.is_set():
                    self._cond.wait(0.1)
                fresh = self.tokens[i:]
                finished = self._done_evt.is_set()
                err = self.error
            for tok in fresh:
                yield tok
            i += len(fresh)
            if finished and i >= len(self.tokens):
                if err is not None:
                    raise err
                return


class _MISSING:  # sentinel: "kwarg not passed" (None is a valid value)
    pass


class DecodeEngine:
    """Continuous-batching decode engine over a paged KV cache.

    Parameters
    ----------
    params : pytree of arrays
        Model parameters, opaque to the engine: handed to the bodies.
    prefill_fn, step_fn, cache_spec : callables, required
        The model (module docstring, "The cache seam"): a family's
        ``engine_kwargs()`` brings them with ``params``.
    cache_pages : callable or None
        The family's ``pages(n)`` (:mod:`.kvcache`); None: a block a
        ``block_size`` positions, held to the sequence's end.
    eos_id : int or None
        Token id that terminates a sequence (emitted, then retired).
    block_size / num_blocks : int
        KV pool geometry (``MXNET_SERVING_DECODE_BLOCK`` /
        ``MXNET_SERVING_DECODE_BLOCKS``). Block 0 is reserved.
    batch_size : int
        Decode slots — THE fixed step shape (``MXNET_SERVING_DECODE_BATCH``).
    max_seq_len : int
        Hard cap on prompt + generated per sequence; fixes the block-
        table width (``MXNET_SERVING_DECODE_MAX_SEQ``).
    prefill_buckets : tuple of int
        Prompt-length buckets (``MXNET_SERVING_DECODE_BUCKETS``,
        comma-separated). One prefill program per bucket.
    default_deadline_ms : float or None
        Deadline applied when ``submit`` passes none
        (``MXNET_SERVING_DECODE_DEADLINE_MS``; unset/0 = no deadline).
    prefill_chunk : int or None
        Chunked-prefill piece size
        (``MXNET_SERVING_DECODE_PREFILL_CHUNK``; 0 disables). Resolved
        DOWN to a prefill bucket so chunk programs reuse the family.
    mesh / kv_shard_axis : jax.sharding.Mesh or None / str
        When given, every pool is placed with the sharding its leaf
        of ``cache_spec`` carries, else with
        :func:`~.kvcache.page_sharding` (trailing model dim sharded
        over ``kv_shard_axis`` when divisible — heads, for the
        transformer layout), and params are replicated on the mesh.
        Params keep the dtype they arrive in.

    All env vars are read once here — never per step (zero-overhead
    contract). ``warmup=True`` AOT-compiles the full program family at
    construction so the loop never compiles.
    """

    def __init__(self, params, *, prefill_fn, step_fn, cache_spec,
                 cache_pages=None, name="decode", eos_id=None,
                 block_size=None, num_blocks=None, batch_size=None,
                 max_seq_len=None, prefill_buckets=None,
                 default_deadline_ms=_MISSING, default_max_new=None,
                 prefill_chunk=None, mesh=None, kv_shard_axis="tp",
                 warmup=True, autostart=True):
        import jax
        import jax.numpy as jnp
        from ..compile.builder import ProgramBuilder
        from .program_cache import _donate_supported

        self.name = name
        self.eos_id = eos_id
        if block_size is None:
            block_size = get_env("MXNET_SERVING_DECODE_BLOCK", 16, int)
        if num_blocks is None:
            num_blocks = get_env("MXNET_SERVING_DECODE_BLOCKS", 64, int)
        if batch_size is None:
            batch_size = get_env("MXNET_SERVING_DECODE_BATCH", 4, int)
        if max_seq_len is None:
            max_seq_len = get_env("MXNET_SERVING_DECODE_MAX_SEQ", 256, int)
        if prefill_buckets is None:
            raw = get_env("MXNET_SERVING_DECODE_BUCKETS",
                          ",".join(str(b) for b in DEFAULT_DECODE_BUCKETS))
            prefill_buckets = tuple(sorted(
                int(t) for t in raw.split(",") if t.strip()))
        if default_deadline_ms is _MISSING:
            default_deadline_ms = get_env(
                "MXNET_SERVING_DECODE_DEADLINE_MS", None, float)
            if default_deadline_ms is not None and default_deadline_ms <= 0:
                default_deadline_ms = None
        if default_max_new is None:
            default_max_new = get_env("MXNET_SERVING_DECODE_MAX_NEW", 32, int)
        if prefill_chunk is None:
            prefill_chunk = get_env("MXNET_SERVING_DECODE_PREFILL_CHUNK",
                                    0, int)
        self.batch_size = int(batch_size)
        self.max_seq_len = int(max_seq_len)
        self.prefill_buckets = tuple(b for b in prefill_buckets
                                     if b <= self.max_seq_len) or (
                                         self.max_seq_len,)
        self.default_deadline_ms = default_deadline_ms
        self.default_max_new = int(default_max_new)
        # chunked prefill: resolve the requested chunk DOWN to a bucket
        # so chunk programs come from the existing prefill family and
        # program_count stays len(buckets) + 1. 0 disables chunking.
        cands = [b for b in self.prefill_buckets if b <= int(prefill_chunk)]
        self.prefill_chunk = cands[-1] if (int(prefill_chunk) > 0
                                           and cands) else 0

        self._kv = PagedKVCache(num_blocks, block_size, cache_pages)
        self._mb = self._kv.table_width(self.max_seq_len)
        spec = cache_spec(self._kv.num_blocks, self._kv.block_size,
                          self.batch_size)
        # what the two kinds of leaf hold, before placement rebuilds them
        from ..models.decode_model import SlotPool
        for p in jax.tree_util.tree_leaves(spec):
            nbytes = math.prod(p.shape) * jnp.dtype(p.dtype).itemsize
            if isinstance(p, SlotPool):
                self._kv.state_bytes += nbytes
            else:
                self._kv.pool_bytes += nbytes
        self._params = jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, params))
        # tp-shardable pools: a leaf that carries its own sharding keeps
        # it (a latent row has no head axis to shard); any other gets
        # kvcache.page_sharding — the trailing model dim over
        # kv_shard_axis when divisible, so multi-head K/V, heads folded
        # into the trailing dim, shards by head whatever axes the model
        # puts in front of it. Params are replicated on the mesh.
        self._kv_shard_axis = str(kv_shard_axis)
        self._meshed = mesh is not None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from .kvcache import page_sharding
            spec = jax.tree_util.tree_map(
                lambda p: jax.ShapeDtypeStruct(
                    p.shape, p.dtype,
                    sharding=p.sharding or page_sharding(
                        mesh, p.shape, kv_shard_axis)), spec)
            self._params = jax.device_put(
                self._params, NamedSharding(mesh, PartitionSpec()))
        self._cache_spec = spec
        # the pools are born on the device in their own dtype and
        # sharding: one program, no host copy, no float32 twin
        shardings = jax.tree_util.tree_map(lambda p: p.sharding, spec) \
            if self._meshed else None
        self._cache = jax.jit(
            lambda: jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, p.dtype), spec),
            out_shardings=shardings)()
        # the cache is consumed and replaced every call — donate it back
        # to XLA where the backend supports it (not host CPU)
        donate = (1,) if _donate_supported() else ()
        self._prefill_b = ProgramBuilder(
            prefill_fn, site="decode.prefill.%s" % name,
            donate_argnums=donate)
        self._step_b = ProgramBuilder(
            step_fn, site="decode.step.%s" % name,
            donate_argnums=donate)

        self._cv = threading.Condition()
        self._waiting = []              # DecodeStream, EDF-ordered at admit
        self._slots = [None] * self.batch_size   # _Seq state per row
        self._stop = False
        self._rid_ctr = 0
        self._counters = {"submitted": 0, "served": 0, "shed": 0,
                          "failed": 0, "tokens": 0, "prefills": 0,
                          "prefill_chunks": 0, "steps": 0, "steps_ahead": 0,
                          "cache_oom": 0}
        self._model = {}                # the bodies' aux, summed
        # the step dispatched and not yet read back: (rows, next_ids, aux,
        # dispatch time), landed by the next _decode_step
        self._ahead = None
        self._device_get = jax.device_get
        self._tree_leaves = jax.tree_util.tree_leaves
        self._lat_step = "decode.%s.step" % name
        self._lat_ttft = "decode.%s.ttft" % name
        self._lat_tok = "decode.%s.intertoken" % name

        if warmup:
            self.warmup()
        self._thread = None
        if autostart:
            self.start()

    # ------------------------------------------------------------------
    # program family
    # ------------------------------------------------------------------
    def warmup(self):
        """AOT-compile the whole family: one prefill per bucket + the
        decode step. After this, steady-state decode never compiles."""
        import jax
        import numpy as np
        i32 = np.int32
        sd = jax.ShapeDtypeStruct
        cache = self._cache_spec
        for bucket in self.prefill_buckets:
            self._prefill_b.aot_info(
                self._params, cache, sd((bucket,), i32),
                sd((), i32), sd((), i32), sd((self._mb,), i32), sd((), i32),
                mode="aot")
        b, mb = self.batch_size, self._mb
        self._step_b.aot_info(
            self._params, cache, sd((b,), i32), sd((b,), i32),
            sd((b, mb), i32), sd((b,), np.bool_), mode="aot")

    def program_counts(self):
        """(prefill_programs, step_programs) — the acceptance counters:
        len(prefill_buckets) and exactly 1, flat while serving."""
        return (self._prefill_b.program_count(), self._step_b.program_count())

    def comm_plan(self):
        """Declared comm contracts for the TPL3xx program audit:
        ``{"prefill": CommPlan, "step": CommPlan}``. Unmeshed engines
        are collective-free; with a mesh, the tp-sharded K/V heads fold
        their partial attention outputs (and the replicated-param
        matmuls their logits) with all-reduces over the kv-shard axis —
        anything on another axis is TPL301. Family cardinality pins to
        len(prefill_buckets) / 1, the same flat-while-serving invariant
        ``program_counts`` asserts."""
        from ..analysis.program_audit import CommPlan
        allowed = ()
        if self._meshed:
            allowed = (("all-reduce", self._kv_shard_axis, None),
                       ("all-gather", self._kv_shard_axis, None))
        return {
            "prefill": CommPlan(site=self._prefill_b.site, allowed=allowed,
                                max_programs=len(self.prefill_buckets)),
            "step": CommPlan(site=self._step_b.site, allowed=allowed,
                             max_programs=1),
        }

    def _bucket_for(self, n):
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return None

    # ------------------------------------------------------------------
    # submission API
    # ------------------------------------------------------------------
    def submit(self, tokens, max_new_tokens=None, deadline_ms=_MISSING,
               priority=0, trace=None, on_token=None, on_done=None):
        """Queue a prompt for decode; returns a :class:`DecodeStream`.

        Raises ``ValueError`` synchronously (nothing counted) for
        prompts the engine can never serve: empty, longer than the
        largest prefill bucket, or leaving no room to generate."""
        flat = _np.asarray(tokens).reshape(-1)  # tpulint: allow-host-sync prompt tokens are host ints, normalized once at submission
        prompt = [int(t) for t in flat]
        if not prompt:
            raise ValueError("empty prompt")
        if self._bucket_for(len(prompt)) is None and not (
                self.prefill_chunk and len(prompt) < self.max_seq_len):
            raise ValueError(
                "prompt of %d tokens exceeds the largest prefill bucket "
                "(%d) and chunked prefill is disabled "
                "(MXNET_SERVING_DECODE_PREFILL_CHUNK)"
                % (len(prompt), self.prefill_buckets[-1]))
        if max_new_tokens is None:
            max_new_tokens = self.default_max_new
        max_new_tokens = min(int(max_new_tokens),
                             self.max_seq_len - len(prompt))
        if max_new_tokens < 1:
            raise ValueError("prompt of %d tokens leaves no room to "
                             "generate (max_seq_len=%d)"
                             % (len(prompt), self.max_seq_len))
        if deadline_ms is _MISSING:
            deadline_ms = self.default_deadline_ms
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms is not None else None)
        with _prof.span("mx.decode.submit", prompt_len=len(prompt)) as sp, \
                self._cv:
            if self._stop:
                raise RuntimeError("decode engine %s is stopped" % self.name)
            self._rid_ctr += 1
            stream = DecodeStream("%s-%d" % (self.name, self._rid_ctr),
                                  prompt, max_new_tokens, deadline, priority,
                                  trace=trace, on_token=on_token,
                                  on_done=on_done)
            stream._order = self._rid_ctr
            # reckoned once: the formation pass looks at every waiter
            # every iteration. What its admission needs free (the most its
            # prefill pieces hold at once) and what it can never do without
            via = self._piece_ends(len(prompt))
            stream._admit_blocks = self._kv.blocks_for(len(prompt), via)
            stream._least_blocks = self._kv.blocks_for(len(prompt) + 1, via)
            self._counters["submitted"] += 1
            self._waiting.append(stream)
            self._cv.notify_all()
            sp.set_metadata(rid=stream.rid)
        _prof.record_decode_event(submitted=1)
        return stream

    def generate(self, tokens, max_new_tokens=None, timeout=60.0, **kw):
        """Blocking convenience: submit and wait for the full output."""
        return self.submit(tokens, max_new_tokens, **kw).result_wait(timeout)

    # ------------------------------------------------------------------
    # loop
    # ------------------------------------------------------------------
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return
        self._thread = threading.Thread(
            target=self._loop, name="mx-decode-%s" % self.name, daemon=True)
        self._thread.start()

    def stop(self, timeout=10.0):
        """Stop the loop; unfinished work resolves failed (counted)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        leftovers = []
        with self._cv:
            leftovers.extend(self._waiting)
            self._waiting = []
            for i, seq in enumerate(self._slots):
                if seq is not None:
                    leftovers.append(seq)
                    self._slots[i] = None
            self._ahead = None
        for s in leftovers:
            self._kv.free(s.rid)
            self._finish(s, RuntimeError("decode engine stopped"))

    def _finish(self, stream, error=None):
        """Resolve a stream exactly once + count the outcome."""
        if not stream._resolve(error):
            return
        key = stream.outcome
        with self._cv:
            self._counters[key] += 1
            if isinstance(error, CacheOverflow):
                self._counters["cache_oom"] += 1
        _prof.record_decode_event(
            **({key: 1, "cache_oom": 1} if isinstance(error, CacheOverflow)
               else {key: 1}))

    def _loop(self):
        from ..resilience.watchdog import watchdog as _watchdog
        hb = _watchdog().register("mx-decode-%s" % self.name,
                                  thread=threading.current_thread())
        try:
            n = 0
            while self._iterate(hb, n):
                n += 1
        finally:
            hb.close()

    def _idle(self):
        return not (self._stop or self._waiting
                    or any(s is not None for s in self._slots))

    def _iterate(self, hb, n):
        """Pass ``n`` of the loop: idle wait, formation, the admitted
        sequences' prefills, one step. False once the engine is stopped.
        Every boundary is a ``profiler.span`` (docs/faq/perf.md): the
        leaves tile the pass, so a device gap in a trace has a name."""
        with _prof.span("mx.decode.iteration", n=n):
            with _prof.span("mx.decode.admit") as sp:
                with self._cv:
                    if self._idle():
                        with _prof.span("mx.decode.wait"):
                            while self._idle():
                                hb.idle()
                                self._cv.wait(0.05)
                    if self._stop:
                        return False
                    hb.beat()
                    waiting = len(self._waiting)
                    sheds, rejects, admitted = self._form_batch_locked()
                for s in sheds + rejects:
                    self._finish(s, s._shed_err)
                sp.set_metadata(waiting=waiting, admitted=len(admitted),
                                shed=len(sheds) + len(rejects))
            for s in admitted:
                self._prefill_one(s)
            self._decode_step()
        return True

    def _form_batch_locked(self):
        """The formation pass (EDF, generalizing the batcher): shed
        expired waiters, reject never-fit prompts, admit into free slots
        while their prompts fit the pool beside the batch's next step.
        Runs under ``_cv`` — host bookkeeping only, no device calls
        (TPL104)."""
        now = time.monotonic()
        sheds, rejects = [], []
        keep = []
        for s in self._waiting:
            if s.deadline is not None and now > s.deadline:
                s._shed_err = DeadlineExceeded(
                    "decode %s: deadline expired before admission" % s.rid)
                sheds.append(s)
            elif s._least_blocks > self._kv.capacity_blocks:
                s._shed_err = CacheOverflow(
                    "decode %s: prompt of %d tokens can never fit a pool "
                    "of %d blocks" % (s.rid, len(s.prompt),
                                      self._kv.capacity_blocks))
                rejects.append(s)
            else:
                keep.append(s)
        # highest priority first, then earliest deadline, then arrival
        keep.sort(key=lambda s: (-s.priority,
                                 s.deadline if s.deadline is not None
                                 else float("inf"), s._order))
        admitted = []
        free = [i for i, s in enumerate(self._slots) if s is None]
        # beside a prompt's own need, what the next step of the rows
        # already in the batch may take stays free: a prompt's pages are
        # taken for as long as its prefill lasts, and a pool that admission
        # fills to its last block fails the rows that grow meanwhile
        kv, held = self._kv, self.batch_size - len(free)
        still_waiting = []
        for s in keep:
            if free and s._admit_blocks + kv.regions * held \
                    <= kv.free_blocks:
                # with what its earlier pieces are written through and the
                # whole prompt no longer needs (a window that closes inside
                # the prompt), taken HERE so the next waiter is tested
                # against what is left; handed back at its first step.
                # Nothing more for a table that only grows
                kv.allocate(s.rid, len(s.prompt),
                            self._piece_ends(len(s.prompt)))
                s._slot = free.pop(0)
                self._slots[s._slot] = s
                admitted.append(s)
                held += 1
            else:
                still_waiting.append(s)
        self._waiting = still_waiting
        return sheds, rejects, admitted

    def _piece_ends(self, n):
        """The lengths a prompt of ``n`` tokens passes through before its
        last prefill piece lands (`_prefill_one` cuts it the same way)."""
        chunk = self.prefill_chunk
        return range(chunk, n, chunk) if chunk and n > chunk else ()

    def _evict(self, stream, error):
        """Drop an ACTIVE sequence: free its blocks, vacate its slot,
        resolve the outcome."""
        self._kv.free(stream.rid)
        self._slots[stream._slot] = None
        self._finish(stream, error)

    def _prefill_one(self, stream):
        """Run the bucketed prefill program(s) for one admitted sequence
        and emit its first token (device calls — outside ``_cv``).

        Chunked prefill: when ``prefill_chunk`` is set and the prompt is
        longer, the prompt runs as chunk-bucket-sized pieces through the
        SAME program family, and one continuous-batching step runs for
        the other active sequences between pieces — a long prompt no
        longer stalls the step loop. The sequence stays invisible to the
        step loop until its last piece lands (``_cached`` is None), and
        per-chunk deadline checks shed typed mid-prefill."""
        prompt = stream.prompt
        chunk = self.prefill_chunk
        if chunk and len(prompt) > chunk:
            pieces = [prompt[i:i + chunk]
                      for i in range(0, len(prompt), chunk)]
        else:
            pieces = [prompt]
        with _prof.span("mx.decode.prefill", rid=stream.rid,
                        prompt_len=len(prompt), pieces=len(pieces)):
            self._prefill_pieces(stream, pieces)

    def _prefill_pieces(self, stream, pieces):
        prompt = stream.prompt
        table = _np.zeros((self._mb,), _np.int32)
        own = self._kv.table(stream.rid)
        table[:len(own)] = own
        start = 0
        tok = None
        auxes = []                      # every piece's, read with the id
        for pi, piece in enumerate(pieces):
            last = pi == len(pieces) - 1
            if pi and stream.deadline is not None \
                    and time.monotonic() > stream.deadline:
                self._evict(stream, DeadlineExceeded(
                    "decode %s: deadline exceeded mid-prefill after %d of "
                    "%d prompt tokens" % (stream.rid, start, len(prompt))))
                return
            bucket = self._bucket_for(len(piece))
            toks = _np.zeros((bucket,), _np.int32)
            toks[:len(piece)] = piece
            _faults.fault_point("decode.step", model=self.name,
                                kind="prefill", rid=stream.rid)
            try:
                with _prof.span("mx.decode.prefill.dispatch", bucket=bucket):
                    next_id, self._cache, aux = self._prefill_b(
                        self._params, self._cache, toks,
                        _np.int32(start), _np.int32(len(piece)), table,
                        _np.int32(stream._slot))
                auxes.append(aux)
                if last:
                    with _prof.span("mx.decode.prefill.readback",
                                    bucket=bucket):
                        next_id, auxes = self._device_get((next_id, auxes))  # tpulint: allow-host-sync sampled token feeds the next step and the reply stream; decode cannot proceed without it
                        tok = int(next_id)
            except Exception as e:
                self._evict(stream, e if isinstance(e, DeadlineExceeded)
                            else RuntimeError(
                                "decode prefill failed: %s" % e))
                return
            start += len(piece)
            if not last:
                self._decode_step()
        now = time.monotonic()
        stream.first_token_t = stream.last_token_t = now
        stream._cached = len(prompt)    # positions 0..len-1 hold K/V
        _prof.record_latency(self._lat_ttft,
                             int((now - stream.submitted_t) * 1e9))
        with self._cv:
            self._counters["prefills"] += 1
            self._counters["tokens"] += 1
            if len(pieces) > 1:
                self._counters["prefill_chunks"] += len(pieces)
            for aux in auxes:
                self._count_aux_locked(aux)
        _prof.record_decode_event(prefills=1, tokens=1)
        stream._emit(tok)
        self._maybe_retire(stream, tok)

    def _count_aux_locked(self, aux):
        """Sum one call's ``aux`` (host integers by now) into the
        ``stats()["model"]`` counters. Runs under ``_cv``."""
        for k, v in aux.items():
            self._model[k] = self._model.get(k, 0) + int(v)

    def _maybe_retire(self, stream, last_tok):
        """Retire on EOS or token budget; returns True when retired."""
        if ((self.eos_id is not None and last_tok == self.eos_id)
                or len(stream.tokens) >= stream.max_new_tokens):
            self._kv.free(stream.rid)
            self._slots[stream._slot] = None
            self._finish(stream, None)
            return True
        return False

    def _live(self):
        # _cached is None while a sequence's prefill is still in flight
        # (chunked prefill steps the loop between pieces) — such rows
        # must be invisible to the step: no deadline eviction (the
        # prefill loop owns it), no growth, no step slot.
        return [s for s in self._slots
                if s is not None and s._cached is not None]

    def _decode_step(self):
        """One continuous-batching iteration over the active slots:
        per-token deadline enforcement, cache growth (typed shed on
        overflow), one fixed-shape step program call, distribution.

        The loop runs ONE STEP AHEAD of the host. A dispatched step stays
        in flight (``_ahead``) and the next call lands it: reads its ids
        back, emits them, retires what finished. Where the next step
        needs nothing of that (``_follows``) it is dispatched FIRST, its
        ``token_ids`` the in-flight step's ``next_ids`` still on the
        device, so the host's work on step N (the read-back, a callback
        and two latency records a row, growth, the admission pass) runs
        while the device runs step N + 1. Otherwise the step in flight
        lands first and the call is the synchronous iteration it always
        was. The programs, their inputs and every sequence's tokens are
        the same either way."""
        with _prof.span("mx.decode.step") as step_sp:
            ahead = self._ahead
            if ahead is not None and not self._follows(ahead):
                self._land(ahead)
                ahead = None
            with _prof.span("mx.decode.step.grow") as sp:
                if ahead is None:
                    now = time.monotonic()
                    for seq in self._live():
                        if seq.deadline is not None and now > seq.deadline:
                            self._evict(seq, DeadlineExceeded(
                                "decode %s: deadline exceeded after %d tokens"
                                % (seq.rid, len(seq.tokens))))
                active = []
                for seq in self._live():
                    if seq._inflight is not None \
                            and len(seq.tokens) + 1 >= seq.max_new_tokens:
                        continue        # the token in flight is its last
                    try:
                        # room for the token this step writes at _cached
                        self._kv.extend(seq.rid, 1)
                        active.append(seq)
                    except CacheOverflow as e:
                        self._evict(seq, e)
                sp.set_metadata(rows=len(active))
            step_sp.set_metadata(active=len(active))
            if not active:
                if ahead is not None:
                    self._land(ahead)
                return
            with _prof.span("mx.decode.step.pack"):
                b, mb = self.batch_size, self._mb
                positions = _np.zeros((b,), _np.int32)
                tables = _np.zeros((b, mb), _np.int32)
                mask = _np.zeros((b,), _np.bool_)
                for seq in active:
                    i = seq._slot
                    positions[i] = seq._cached
                    own = self._kv.table(seq.rid)
                    tables[i, :len(own)] = own
                    mask[i] = True
                if ahead is None:
                    token_ids = _np.zeros((b,), _np.int32)
                    for seq in active:
                        token_ids[seq._slot] = seq.tokens[-1]
                else:
                    # row i of the step in flight IS slot i of this one; a
                    # row it did not step is masked here too
                    token_ids = ahead[1]
            _faults.fault_point("decode.step", model=self.name, kind="step",
                                batch=len(active))
            t0 = time.monotonic()
            try:
                with _prof.span("mx.decode.step.dispatch"):
                    next_ids, self._cache, aux = self._step_b(
                        self._params, self._cache,
                        token_ids, positions, tables, mask)
            except Exception as e:
                self._fail_step(active, e)
                if ahead is not None:
                    self._land(ahead)
                return
            # ask for the ids NOW: a copy to the host asked for once the
            # next step is queued waits behind that step on the device
            for out in self._tree_leaves((next_ids, aux)):
                out.copy_to_host_async()
            self._ahead = step = (active, next_ids, aux, t0)
            for seq in active:
                seq._cached += 1        # the write at _cached is queued
                seq._inflight = step
            if ahead is not None:
                self._land(ahead)

    def _follows(self, ahead):
        """Whether the next step can be dispatched behind the one in
        flight BEFORE its tokens are read: every live row takes its token
        from it (none is fresh from a prefill, its token on the host), no
        deadline has passed (an eviction comes after the tokens it
        follows), and the pool holds the blocks every row's next position
        takes (no growth can overflow)."""
        live = self._live()
        now = time.monotonic()
        kv = self._kv
        # (a position more takes at most a block a region of the table:
        # only a pool that near to full is reckoned row by row)
        room = kv.free_blocks
        return ((room >= kv.regions * len(live)
                 or room >= sum(kv.growth(s.rid) for s in live))
                and all(s._inflight is ahead
                        and (s.deadline is None or now <= s.deadline)
                        for s in live))

    def _fail_step(self, rows, e):
        # step state is unknown after a failed dispatch or read-back: fail
        # the whole active set (chaos tests drive this via decode.step)
        err = e if isinstance(e, DeadlineExceeded) else RuntimeError(
            "decode step failed: %s" % e)
        for seq in rows:
            if self._slots[seq._slot] is seq:
                self._evict(seq, err)

    def _land(self, step):
        """Read a dispatched step's ids back, emit them, retire what
        finished. A row that left its slot since the dispatch (it ended
        on the token before, which only the host could see) is passed
        over."""
        rows, next_ids, aux, t0 = step
        if self._ahead is step:
            self._ahead = None
        try:
            with _prof.span("mx.decode.step.readback"):
                ids, aux = self._device_get((next_ids, aux))  # tpulint: allow-host-sync sampled tokens feed the reply streams and the retirements; a step behind it may already be queued
        except Exception as e:
            # whatever was dispatched behind it read the same cache
            later, self._ahead = self._ahead, None
            self._fail_step(rows + (later[0] if later else []), e)
            return
        with _prof.span("mx.decode.step.emit") as sp:
            now = time.monotonic()
            step_ns = int((now - t0) * 1e9)
            _prof.record_latency(self._lat_step, step_ns)
            stepped = len(rows)
            rows = [s for s in rows if self._slots[s._slot] is s]
            with self._cv:
                self._counters["steps"] += 1
                # its successor was queued before this one was read
                self._counters["steps_ahead"] += self._ahead is not None
                self._counters["tokens"] += len(rows)
                self._count_aux_locked(aux)
            _prof.record_decode_event(steps=1, tokens=len(rows),
                                      slot_steps=stepped,
                                      slot_capacity=self.batch_size)
            retired = []
            for seq in rows:
                if seq._inflight is step:
                    seq._inflight = None
                tok = int(ids[seq._slot])
                if seq.last_token_t is not None:
                    _prof.record_latency(
                        self._lat_tok,
                        int((now - seq.last_token_t) * 1e9))
                seq.last_token_t = now
                seq._emit(tok)
                if self._maybe_retire(seq, tok):
                    retired.append(seq.rid)
            sp.set_metadata(retired=len(retired), rid=",".join(retired))

    # ------------------------------------------------------------------
    def stats(self):
        """Counters + cache occupancy + program family sizes; ``model``
        holds the bodies' ``aux`` summed over every call so far."""
        with self._cv:
            out = dict(self._counters)
            out["waiting"] = len(self._waiting)
            out["active"] = sum(1 for s in self._slots if s is not None)
            out["model"] = dict(self._model)
        out["kv"] = self._kv.stats()
        pf, st = self.program_counts()
        out["programs"] = {"prefill": pf, "step": st}
        sites = _prof.compile_counters()["sites"]
        out["compile"] = {
            "prefill": sites.get("decode.prefill.%s" % self.name, {}),
            "step": sites.get("decode.step.%s" % self.name, {})}
        return out
