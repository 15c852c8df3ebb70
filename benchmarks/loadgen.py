"""The one load generator: a traffic mix is a data file under
``benchmarks/traffic/`` and this module turns it, with a seed, into work.

Every seed gets THE SAME sizes and arrival gaps (drawn once from the
mix's own ``base_seed``) in another order, so that seeds never change the
amount of work.  The other order is a ROTATION of the one drawn sequence
(by ``seed`` modulo its length), not a shuffle: a rotation keeps every
request among the same neighbours and moves only where the window
starts.  On the chip (PR 24) the closed loop's p95 first-token time
spread by 1 % under either, but a shuffle of the same 96 requests moved
its tokens a second by 6-8 % where rotations moved it by 2.6 %, and an
open loop's p95 first-token time between 1.2 and 3.0 s: which long
prompts meet decides those, not the system.  PERF.md section 2 says what
that leaves a bound unable to see.  Prompt tokens are drawn from the
seed itself.

Both loops are here, closed (``clients``) and open (``rate_per_s``,
Poisson), so that a later cell of either kind is a data file.
"""

from __future__ import annotations

import numpy as np


def _rng(*key):
    return np.random.default_rng([int(k) % (2 ** 63) for k in key])


def lognormal_lengths(spec, rng, n):
    """``n`` lengths, log-normal with the stated median and sigma,
    clipped to [lo, hi] and rounded up to a multiple of ``round_to``."""
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    step = int(spec.get("round_to", 1))
    x = np.ceil(np.clip(x, spec["lo"], spec["hi"]) / step) * step
    return np.clip(x, spec["lo"], spec["hi"]).astype(np.int64)


def request_sizes(mix, seed, n):
    """[(prompt_len, output_len)] * n: the mix's fixed sequence, started
    at ``seed % n``."""
    base = _rng(mix.get("base_seed", 0), 1)
    pairs = np.stack([lognormal_lengths(mix["prompt_len"], base, n),
                      lognormal_lengths(mix["output_len"], base, n)], 1)
    return [tuple(int(v) for v in p) for p in np.roll(pairs, -(seed % n), 0)]


def poisson_arrivals(mix, seed, start_s, end_s):
    """Due times in [start_s, end_s) of a Poisson stream at
    ``mix["rate_per_s"]``: round(rate * span) exponential gaps from the
    base seed, scaled to fill the span, started at ``seed % n`` (the same
    rotation as ``request_sizes``: a request keeps its gaps)."""
    span = end_s - start_s
    n = max(int(round(mix["rate_per_s"] * span)), 1)
    gaps = _rng(mix.get("base_seed", 0), 3).exponential(1.0, n)
    gaps = np.roll(gaps * (span / gaps.sum()), -(seed % n))
    return (start_s + np.cumsum(gaps) - gaps).tolist()


def prompt_tokens(seed, index, length, vocab):
    return _rng(seed, 5, index).integers(0, vocab, length).astype(np.int32)


def train_batches(mix, seed, vocab):
    """``mix["pool"]`` batches of the synthetic next-token task of
    ``chip_smoke.synthetic_batches`` (next = 3 * token + 7 modulo the ids
    in use; the data uses ``data_ids`` of the vocabulary so that a working
    optimizer shows a falling loss within a window)."""
    rng = _rng(seed, 6)
    ids = min(int(mix["data_ids"]), vocab)
    out = []
    for _ in range(int(mix["pool"])):
        x = rng.integers(0, ids, (mix["batch"], mix["seq"])).astype(np.int32)
        out.append((x, ((3 * x + 7) % ids).astype(np.int32)))
    return out


def percentile(values, q):
    """numpy's linear percentile; None for an empty sample."""
    return float(np.percentile(values, q)) if len(values) else None
