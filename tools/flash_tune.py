#!/usr/bin/env python
"""Flash-attention kernel validation + block-size sweep on real TPU.

Run when a chip is available:
    python tools/flash_tune.py            # full sweep @ S=4096
    python tools/flash_tune.py --quick    # one config, parity only

Per config it (1) compiles the Pallas fwd AND bwd kernels non-interpret,
(2) checks parity against the blockwise jnp path at fp32 and bf16, and
(3) reports fwd / fwd+bwd TFLOP/s at S=4096, D=128.

Every timed call gets a distinct q (tools/attn_timing.py).
"""
import argparse
import itertools
import json
import time

import numpy as np


def _parity(jax, jnp, flash, blockwise, dtype, tol, variant="stream",
            block_q=None, block_k=None):
    """fwd+bwd agreement between the Pallas kernel and the jnp path."""
    rng = np.random.RandomState(0)
    B, H, S, D = 1, 2, 1024, 128
    q, k, v = (jnp.asarray(rng.normal(0, 1, (B, H, S, D)).astype(np.float32),
                           dtype=dtype) for _ in range(3))
    blocks = {}
    if block_q is not None:
        blocks = {"block_q": block_q, "block_k": block_k}

    def loss_pallas(q, k, v):
        return (flash(q, k, v, causal=True, use_pallas=True,
                      variant=variant, **blocks) ** 2).sum()

    def loss_ref(q, k, v):
        out, _ = blockwise(q, k, v, causal=True, block_k=256)
        return (out ** 2).sum()

    gp = jax.jit(jax.grad(loss_pallas, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b in zip("q k v".split(), gp, gr):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-6
        assert err / scale < tol, ("d%s rel err %.3g (tol %.3g, %s)"
                                   % (name, err / scale, tol, dtype))
    return True


# the one dtype/tolerance table for flash parity everywhere (bench.py's
# flash_parity phase imports run_parity and chip_smoke.py takes its bf16
# bound from here, so no two records disagree about what "parity" means)
PARITY_DTYPES = (("fp32", 2e-3), ("bf16", 4e-2))
DEFAULT_BLOCKS = {"stream": (1024, 512), "grid": (512, 512)}


def load_pinned_blocks(path):
    """{variant: (block_q, block_k)} winners from a flash_tune pin file."""
    import json as _json
    try:
        with open(path) as f:
            best = _json.load(f).get("best_by_variant") or {}
        return {v: (r["block_q"], r["block_k"]) for v, r in best.items()}
    except (OSError, ValueError, KeyError, TypeError):
        return {}


def run_parity(jax, jnp, flash, blockwise, pinned_blocks=None):
    """Non-interpret fwd+bwd parity of BOTH Pallas families at each
    PARITY_DTYPES entry, using the PINNED production block sizes when
    available (VMEM/layout failures are block-size dependent — validating
    only defaults would miss regressions in the config the bench runs).
    Returns {key: True | 'Error: ...'} per (variant, dtype)."""
    out = {}
    for variant in ("stream", "grid"):
        bq, bk = (pinned_blocks or {}).get(variant,
                                           DEFAULT_BLOCKS[variant])
        for name, tol in PARITY_DTYPES:
            dtype = jnp.float32 if name == "fp32" else jnp.bfloat16
            key = "flash_parity_%s_%s" % (variant, name)
            try:
                _parity(jax, jnp, flash, blockwise, dtype, tol,
                        variant=variant, block_q=bq, block_k=bk)
                out[key] = True
            except Exception as e:  # noqa: BLE001 — recorded, not masked
                out[key] = "%s: %s" % (type(e).__name__, str(e)[:140])
    return out


def main():
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--budget-s", type=int, default=0,
                    help="stop sweeping when exceeded (0 = no cap); "
                         "results so far are still written/pinned")
    ap.add_argument("--out", default=os.path.join(repo,
                                                  "flash_tune_results.json"),
                    help="pin file: bench.py's flash phase and future runs "
                         "read the per-variant winners from here")
    args = ap.parse_args()
    t0 = time.time()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.kernels.flash_attention import (
        flash_attention, blockwise_attention, default_use_pallas)

    dev = jax.devices()[0]
    print("device:", dev.platform, getattr(dev, "device_kind", ""))
    print("default_use_pallas:", default_use_pallas())
    assert default_use_pallas(), "not on a TPU backend — nothing to tune"

    # on-chip (non-interpret) fwd+bwd parity for BOTH kernel families at
    # the pinned production block sizes — the record CI's interpret-mode
    # runs cannot produce
    parity = run_parity(jax, jnp, flash_attention, blockwise_attention,
                        pinned_blocks=load_pinned_blocks(args.out))
    print("parity:", json.dumps(parity))
    parity_ok = all(v is True for v in parity.values())

    def _write_out(results, note=""):
        ok = [r for r in results if "fwd_tflops" in r]
        best_by_variant = {}
        for r in ok:
            cur = best_by_variant.get(r["variant"])
            if cur is None or r["fwd_tflops"] > cur["fwd_tflops"]:
                best_by_variant[r["variant"]] = r
        # a parity-only (--quick) or budget-capped run must never clobber
        # winners an earlier full sweep pinned: carry forward any variant
        # this run didn't (re-)measure
        try:
            with open(args.out) as f:
                prior = json.load(f).get("best_by_variant") or {}
            for vname, row in prior.items():
                best_by_variant.setdefault(vname, row)
        except (OSError, ValueError, AttributeError):
            pass
        import subprocess
        commit = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                                cwd=repo, capture_output=True,
                                text=True).stdout.strip()
        payload = {
            "device": "%s %s" % (dev.platform,
                                 getattr(dev, "device_kind", "")),
            "commit": commit, "ts": round(time.time(), 1),
            "seq": args.seq, "parity_nonintrp_fwd_bwd": parity,
            "note": note, "results": results,
            "best_by_variant": best_by_variant,
            "best": (max(best_by_variant.values(),
                         key=lambda r: r["fwd_tflops"])
                     if best_by_variant else None),
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print("pinned -> %s" % args.out, flush=True)
        return payload

    if args.quick:
        _write_out([], note="--quick: parity only, no sweep")
        if not parity_ok:
            raise SystemExit("parity failures: %s" % json.dumps(parity))
        return

    import sys as _sys
    _sys.path.insert(0, repo)
    from tools import attn_timing  # shared methodology with bench.py

    B, H, S, D = 4, 8, args.seq, 128
    n_iter = 16
    qs, k, v = attn_timing.make_inputs(B, H, S, D, n_iter, jnp.bfloat16)
    flops_fwd = attn_timing.causal_flops(B, H, S, D)

    # anchor: the jnp blockwise path (pure XLA fusion, no Pallas) on the
    # same shapes — tells us how much the hand-written kernel actually buys
    try:
        bw_tf, _ = attn_timing.timed_map_tflops(
            lambda q, k_, v_: blockwise_attention(q, k_, v_, causal=True,
                                                  block_k=512)[0],
            qs, k, v, flops_fwd * n_iter)
        print(json.dumps({"xla_blockwise_fwd_tflops": round(bw_tf, 2)}),
              flush=True)
    except Exception as e:
        print(json.dumps({"xla_blockwise_error": str(e)[:120]}), flush=True)

    # likely winners first so a --budget-s cap (brief chip window) still
    # pins a sensible config for every family
    _PRIORITY = ((1024, 512), (512, 512), (1024, 1024), (2048, 512),
                 (512, 1024), (256, 256))
    _rest = [c for c in itertools.product((256, 512, 1024, 2048), repeat=2)
             if c not in _PRIORITY]
    results = []
    for variant, (bq, bk) in itertools.product(
            ("stream", "grid"), list(_PRIORITY) + _rest):
        if bq > S or bk > S:
            continue
        if args.budget_s and time.time() - t0 > args.budget_s:
            print("[tune] budget exhausted; stopping sweep", flush=True)
            break
        try:
            fwd_tf, _ = attn_timing.timed_map_tflops(
                lambda q, k_, v_, bq=bq, bk=bk, fv=variant: flash_attention(
                    q, k_, v_, causal=True, block_q=bq, block_k=bk,
                    use_pallas=True, variant=fv),
                qs, k, v, flops_fwd * n_iter)

            def loss(q_, k_, v_, bq=bq, bk=bk, fv=variant):
                return (flash_attention(q_, k_, v_, causal=True, block_q=bq,
                                        block_k=bk, use_pallas=True,
                                        variant=fv)
                        ** 2).sum()
            bwd_tf, _ = attn_timing.timed_map_tflops(
                lambda q, k_, v_, bq=bq, bk=bk: jax.grad(
                    loss, argnums=(0, 1, 2))(q, k_, v_),
                qs, k, v, 3.5 * flops_fwd * n_iter)
            row = {"variant": variant, "block_q": bq, "block_k": bk,
                   "fwd_tflops": round(fwd_tf, 2),
                   "fwd_bwd_tflops": round(bwd_tf, 2)}
        except Exception as e:
            row = {"variant": variant, "block_q": bq, "block_k": bk,
                   "error": "%s: %s" % (type(e).__name__, str(e)[:120])}
        print(json.dumps(row), flush=True)
        results.append(row)

    payload = _write_out(results)
    if payload["best"] is not None:
        print("BEST:", json.dumps(payload["best"]))
    if not parity_ok:
        raise SystemExit("parity failures: %s" % json.dumps(parity))


if __name__ == "__main__":
    main()
