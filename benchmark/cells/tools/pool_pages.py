#!/usr/bin/env python3
"""Host replay of ``DecodeEngine`` for sizing a backlog cell's pool when the
family says which pages a sequence backs (``cache_pages``: a cache that hands
pages back while the sequence lives). ``tools/pool_model.py`` counts
``ceil(n / block_size)`` itself; this one drives the program's own
`PagedKVCache` with the family's own page function through the engine's
admission (a prompt is admitted when the most its prefill pieces need fits,
and TAKES that at admission, to its first step), chunked prefill (a step
between pieces), growth, the step-ahead landing (a finished row's pages go
back one step late) and retirement, with NO device and NO clock: the order
of those events does not depend on what a step or a piece costs, so what is
read after ``--max-steps`` steps holds for every program that makes no more
steps than that in a window, whatever its speed. Give the steps a window
could hold if a step cost only what the chip's memory bandwidth asks for its
weights and live rows (no program is faster than that); without it the whole
backlog is replayed to its end.

    python3 benchmark/cells/tools/pool_pages.py --config evabyte_l8 \
        --traffic decode_batch_bytes --seeds 60 --max-steps 3061

Every prompt admitted in one pass holds its pieces' pages at once, so a pool
without bound reads, in the ramp, slots x a prompt's most: no size to buy.
What a pool must cover is the high water AFTER the ramp (every slot has made
its first step once: ``after_ramp``), read from a pool without bound; size
``engine.num_blocks`` to its largest plus 9 % (PERF.md, section 4). The same
backlog is then replayed inside ``--num-blocks`` (default: the traffic
file's) and ``row_steps_lost_pct`` says what the bound cost in rows a step
against the pool without bound: 0 where a smaller ramp-time admission only
reorders waiting, since prompts are prefilled one at a time anyway.
"""
import argparse
import collections
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
from harness.traffic import make_requests       # noqa: E402


def page_function(config):
    """The family's own, where the configuration's file says it lives
    (``"cache_pages": "<module>:<class>"``, a class with ``from_dict(config)
    .cache_pages``); a file without the key gets the allocator's default, a
    block a ``block_size`` positions."""
    where = config.get("cache_pages")
    if where is None:
        return None
    module, _, cls = where.partition(":")
    return getattr(importlib.import_module(module),
                   cls).from_dict(config).cache_pages


class _Seq:
    def __init__(self, rid, prompt_len, budget):
        self.rid, self.prompt_len, self.budget = rid, prompt_len, budget
        self.emitted, self.cached, self.inflight = 0, None, None


Replayed = collections.namedtuple(
    "Replayed",
    "high_water steps taken released after_ramp row_steps least_free")


def replay(traffic, config, seed, pages, max_steps=None, num_blocks=None):
    """The backlog's first ``max_steps`` steps (all of it: None) inside a
    pool of ``num_blocks`` (None: without bound): high water in blocks,
    steps, blocks taken, blocks released live, the high water after every
    slot has stepped once, rows summed over the steps, the fewest blocks
    free after a row grew."""
    from mxnet_tpu.serving.kvcache import PagedKVCache
    e = traffic["engine"]
    slots, chunk = e["batch_size"], e["prefill_chunk"]
    if num_blocks is None:
        width = PagedKVCache(2, e["block_size"], pages).table_width(
            e["max_seq_len"])
        num_blocks = slots * width + 2
    kv = PagedKVCache(num_blocks, e["block_size"], pages)
    waiting = [_Seq(i, len(r.prompt), min(r.max_new,
                                          e["max_seq_len"] - len(r.prompt)))
               for i, r in enumerate(make_requests(
                   traffic, config["vocab_size"], seed, 0.0))]
    rows = [None] * slots
    ahead, steps, row_steps = None, 0, 0
    stepped = [False] * slots       # the ramp ends when all are True
    after_ramp, least_free = 0, num_blocks

    def emit(s):
        s.emitted += 1
        if s.emitted >= s.budget:
            kv.free(s.rid)
            rows[rows.index(s)] = None

    def land(step):
        for s in step:
            if s.inflight is step:
                s.inflight = None
            emit(s)

    def live():
        return [s for s in rows if s is not None and s.cached is not None]

    def decode_step():
        nonlocal ahead, steps, row_steps, least_free
        if ahead is not None and not all(s.inflight is ahead
                                         for s in live()):
            land(ahead)
            ahead = None
        active = []
        for s in live():
            if s.inflight is not None and s.emitted + 1 >= s.budget:
                continue
            kv.extend(s.rid, 1)
            least_free = min(least_free, kv.free_blocks)
            stepped[rows.index(s)] = True
            active.append(s)
        prev, ahead = ahead, active or None
        for s in active:
            s.cached += 1
            s.inflight = active
        steps += bool(active)
        row_steps += len(active)
        if prev is not None:
            land(prev)

    while (waiting or any(s is not None for s in rows)) and (
            max_steps is None or steps < max_steps):
        admitted, still = [], []
        for s in waiting:       # every waiter in order; one that does not
            p = s.prompt_len    # fit is passed over, not waited behind
            via = range(chunk, p, chunk) if chunk and p > chunk else ()
            held = slots - rows.count(None)     # their next step's room
            if None in rows and kv.blocks_for(p, via) + kv.regions * held \
                    <= kv.free_blocks:
                kv.allocate(s.rid, p, via)
                rows[rows.index(None)] = s
                admitted.append((s, via))
                if all(stepped):
                    after_ramp = max(after_ramp, kv.live_blocks)
            else:
                still.append(s)
        waiting = still
        for s, via in admitted:
            for _ in via:
                decode_step()
            s.cached = s.prompt_len
            emit(s)
        decode_step()
        if all(stepped):
            after_ramp = max(after_ramp, kv.live_blocks)
    st = kv.stats()
    return Replayed(st["blocks_high_water"], steps, st["allocs"],
                    st["blocks_released_live"], after_ramp, row_steps,
                    least_free)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, default=60)
    ap.add_argument("--max-steps", type=int, default=None)
    ap.add_argument("--num-blocks", type=int, default=None)
    a = ap.parse_args()
    with open(os.path.join(HERE, "traffic", a.traffic + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "configs", a.config + ".json")) as f:
        config = json.load(f)
    pages = page_function(config)
    pool = a.num_blocks or traffic["engine"]["num_blocks"]
    free = [replay(traffic, config, seed, pages, a.max_steps)
            for seed in range(a.seeds)]
    held = [replay(traffic, config, seed, pages, a.max_steps, pool)
            for seed in range(a.seeds)]
    highs = sorted(r.after_ramp for r in free)
    print(json.dumps({
        "seeds": a.seeds, "max_steps": a.max_steps,
        "unbounded_high_water_max": max(r.high_water for r in free),
        "after_ramp_min": highs[0],
        "after_ramp_median": highs[len(highs) // 2],
        "after_ramp_max": highs[-1],
        "plus_9_pct": int(highs[-1] * 1.09) + 1,
        "num_blocks": pool,
        "high_water_in_pool_max": max(r.high_water for r in held),
        "least_free_in_pool": min(r.least_free for r in held),
        "row_steps_lost_pct": max(
            100.0 * (1 - h.row_steps / f.row_steps)
            for h, f in zip(held, free)),
        "steps": sorted(r.steps for r in held)[len(held) // 2],
        "released_live_pct": 100.0 * sum(r.released for r in held)
        / sum(r.taken for r in held)}))


if __name__ == "__main__":
    main()
