#!/usr/bin/env python3
"""Host model of ``DecodeEngine`` for sizing a backlog cell's KV pool: the
engine's admission, chunked prefill, growth and retirement replayed over the
cell's own requests with NO device, given what a step and a prefill piece
cost. Prints, over ``--seeds`` seeds, the high water of live blocks inside the
window (what the pool must hold: the engine sheds a sequence that outgrows
it), the steps made and the requests finished.

    python3 benchmark/cells/tools/pool_model.py --traffic decode_batch_long \
        --vocab 19200 --step-ms 45 --piece-ms 256:22,512:35,1024:60

Size ``engine.num_blocks`` to the largest high water plus 9 % (PERF.md,
section 4), then read ``kv_blocks_high_water_pct`` on the chip against it.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
from harness.traffic import make_requests       # noqa: E402


def replay(traffic, vocab, seed, seconds, step_s, piece_s):
    """(high water in blocks, steps, finished) of one window."""
    e = traffic["engine"]
    bs, slots = e["block_size"], e["batch_size"]
    buckets, chunk = sorted(e["prefill_buckets"]), e["prefill_chunk"]
    blocks = lambda n: -(-n // bs)                      # noqa: E731
    waiting = [(len(r.prompt), r.max_new)
               for r in make_requests(traffic, vocab, seed, seconds)]
    live = []                       # [cached tokens, emitted, budget]
    now, high, steps, finished = 0.0, 0, 0, 0

    def step():
        nonlocal now, steps, finished, high
        if not live:
            return
        now += step_s
        steps += 1
        for s in live:
            s[0] += 1
            s[1] += 1
        high = max(high, sum(blocks(s[0]) for s in live))
        done = [s for s in live if s[1] >= s[2]]
        finished += len(done)
        live[:] = [s for s in live if s[1] < s[2]]

    while now < seconds and (waiting or live):
        admitted = []
        while waiting and len(live) + len(admitted) < slots:
            admitted.append(waiting.pop(0))
        for j, (p, budget) in enumerate(admitted):
            pieces = [min(chunk, p - i) for i in range(0, p, chunk)] \
                if chunk and p > chunk else [p]
            for i, n in enumerate(pieces):
                now += piece_s[next(b for b in buckets if n <= b)]
                if i < len(pieces) - 1:
                    step()
            live.append([p, 1, budget])
            # admission allocated every admitted prompt's blocks at once
            high = max(high, sum(blocks(s[0]) for s in live)
                       + sum(blocks(q) for q, _ in admitted[j + 1:]))
            if now >= seconds:
                break
        step()
    return high, steps, finished


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, default=200)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--piece-ms", required=True,
                    help="bucket:ms,... for each prefill bucket")
    a = ap.parse_args()
    with open(os.path.join(HERE, "traffic", a.traffic + ".json")) as f:
        traffic = json.load(f)
    piece_s = {int(k): float(v) / 1e3 for k, v in
               (kv.split(":") for kv in a.piece_ms.split(","))}
    rows = [replay(traffic, a.vocab, seed, a.seconds, a.step_ms / 1e3,
                   piece_s) for seed in range(a.seeds)]
    highs = sorted(r[0] for r in rows)
    print(json.dumps({
        "seeds": a.seeds, "high_water_min": highs[0],
        "high_water_median": highs[len(highs) // 2],
        "high_water_max": highs[-1],
        "plus_9_pct": int(highs[-1] * 1.09) + 1,
        "steps": sorted(r[1] for r in rows)[len(rows) // 2],
        "finished": sorted(r[2] for r in rows)[len(rows) // 2]}))


if __name__ == "__main__":
    main()
