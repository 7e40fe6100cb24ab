"""The one traffic generator: a traffic mix is a data file of parameters.

A distribution is sampled in one of two ways, named in the file by its
``sample`` key:

``stratified`` (the default): every seed gets the SAME multiset of values in
another order. The values are the mid-quantiles of the distribution, tiled in
blocks of the mix's ``block`` and permuted inside each block by the seed, so
any prefix of whole blocks holds the same work whatever the seed and two runs
differ by order and token ids only. The price: nothing beyond the block's
outermost mid-quantile is ever drawn, and a block of arrival gaps always
spans the same time, so arrivals made this way are NOT a Poisson process
(counts per window barely vary, a burst is at most a block long).

``iid``: independent draws from the seed: a real Poisson process for
exponential gaps, the whole tail for lengths, and a different amount of work
in every seed.
"""
import math
import statistics
from dataclasses import dataclass, field

import numpy as np


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def stratified(dist, n):
    """``n`` values at the mid-quantiles of ``dist``."""
    kind = dist["dist"]
    if kind == "lognormal":
        nd = statistics.NormalDist()
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        vals = [math.exp(mu + sigma * nd.inv_cdf(q)) for q in _quantiles(n)]
    elif kind == "exponential":
        vals = [-dist["mean"] * math.log(1.0 - q) for q in _quantiles(n)]
    else:
        raise ValueError("unknown distribution %r" % kind)
    return _clip(dist, np.asarray(vals, np.float64))


def _clip(dist, vals):
    lo, hi = dist.get("min"), dist.get("max")
    return vals if lo is None and hi is None else np.clip(vals, lo, hi)


def iid(dist, n, rng):
    """``n`` independent draws of ``dist``."""
    kind = dist["dist"]
    if kind == "lognormal":
        vals = rng.lognormal(math.log(dist["median"]), dist["sigma"], n)
    elif kind == "exponential":
        vals = rng.exponential(dist["mean"], n)
    else:
        raise ValueError("unknown distribution %r" % kind)
    return _clip(dist, vals)


def draw(dist, n, block, rng):
    """``n`` values of ``dist`` as its ``sample`` key says: whole blocks of
    the stratified sample, each permuted by the seed, or independent draws."""
    how = dist.get("sample", "stratified")
    if how == "iid":
        return iid(dist, n, rng)
    if how != "stratified":
        raise ValueError("unknown sample %r" % how)
    base = stratified(dist, block)
    out = [rng.permutation(base) for _ in range(-(-n // block))]
    return np.concatenate(out)[:n]


@dataclass
class Request:
    index: int
    due_s: float                  # seconds after the window opens
    prompt: np.ndarray            # int32 token ids
    max_new: int
    sent_s: float = None          # when the generator really sent it
    token_s: list = field(default_factory=list)   # when each token came
    stream: object = None
    error: object = None


def make_requests(traffic, vocab, seed, seconds):
    """The requests of one run. ``kind: backlog``: ``requests`` of them, all
    due at 0. ``kind: open_loop``: arrivals at ``rate_rps`` with exponential
    gaps (``arrivals: {"sample": "iid"}`` makes them a Poisson process, the
    default is the stratified sample), those due inside the window."""
    rng = np.random.default_rng(seed)
    block = int(traffic["block"])
    if traffic["kind"] == "backlog":
        n = int(traffic["requests"])
        due = np.zeros(n)
    elif traffic["kind"] == "open_loop":
        rate = float(traffic["rate_rps"])
        n = block * (int(rate * seconds * 1.25) // block + 2)
        gaps = draw(dict(traffic.get("arrivals", {}), dist="exponential",
                         mean=1.0 / rate), n, block, rng)
        due = np.cumsum(gaps)
        n = int(np.searchsorted(due, seconds))
        due = due[:n]
    else:
        raise ValueError("traffic kind %r makes no requests"
                         % traffic["kind"])
    m = block * (-(-max(n, 1) // block))
    plen = np.rint(draw(traffic["prompt_len"], m, block, rng)).astype(int)
    olen = np.rint(draw(traffic["output_len"], m, block, rng)).astype(int)
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=plen[i], dtype=np.int64)
        reqs.append(Request(i, float(due[i]), prompt.astype(np.int32),
                            int(olen[i])))
    return reqs


def percentile(values, q):
    """The q-th percentile by the nearest-rank rule on the sorted values: the
    value itself, never an interpolation, so a tail is a request that
    happened. ``inf`` among the values (a request that never answered) sorts
    last."""
    if not values:
        return None
    vals = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]
