"""Embedding-cache CTR serving demo (hetu_tpu.serving.embed_engine).

Stands up an in-process PS holding a Criteo-shaped embedding table,
fronts it with the HET ``CacheSparseTable``, and serves a zipf-skewed
click-through scoring trace through the ``EmbedServingEngine``: each
wave gathers 26 sparse-feature embeddings per pair through the cache
(hits local, misses PS-pulled) and scores the whole wave in one jitted
WDL/DCN tower forward.  Cache hit rate, latency percentiles, and the
gather/forward breakdown print at the end.

    python examples/ctr/serve_ctr.py --requests 32 --wave 4

``--kill-ps`` kills the PS for the middle third of the trace: the
cache serves stale rows for warm ids and zero vectors for cold ones,
NOTHING is lost, and the pull counters resume after recovery — the
training degradation protocol doing serving duty:

    python examples/ctr/serve_ctr.py --requests 32 --kill-ps
"""

import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), '..', '..'))

import argparse
import logging

import numpy as np

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu.cache.cstable import CacheSparseTable
from hetu_tpu.ps.client import PSConnectionError
from hetu_tpu.ps.server import PSServer
from hetu_tpu.serving import EmbedRequest, EmbedServingEngine

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
logger = logging.getLogger("serve_ctr")


class _KillablePS:
    """PS wrapper whose verbs raise while ``down`` — the demo's stand-in
    for a real parameter-server outage."""

    def __init__(self, server):
        self._server = server
        self.down = False

    def __getattr__(self, name):
        fn = getattr(self._server, name)

        def wrapper(*a, **kw):
            if self.down:
                raise PSConnectionError("PS down (demo outage)")
            return fn(*a, **kw)
        return wrapper


def build_engine(args):
    server = PSServer()
    server.param_init("snd_order_embedding",
                      (args.vocab, args.embed_dim),
                      "normal", 0.0, 1.0, seed=3)
    comm = _KillablePS(server)
    table = CacheSparseTable(limit=args.cache_limit,
                             vocab_size=args.vocab,
                             width=args.embed_dim,
                             key="snd_order_embedding", comm=comm,
                             policy="LRU")
    rng = np.random.RandomState(0)
    h = 16
    flat = 26 * args.embed_dim
    params = {"W1": rng.randn(13, h) * 0.3,
              "W2": rng.randn(h, h) * 0.3,
              "W3": rng.randn(h, h) * 0.3,
              "W4": rng.randn(flat + h, 1) * 0.3}
    if args.model == "dcn":
        D = flat + 13
        params["W1"] = rng.randn(D, h) * 0.1
        params["W4"] = rng.randn(D + h, 1) * 0.1
        for i in range(3):
            params[f"cross{i}_weight"] = rng.randn(D, 1) * 0.1
            params[f"cross{i}_bias"] = rng.randn(D) * 0.1
    eng = EmbedServingEngine(params, {"snd_order_embedding": table},
                             model=args.model, wave=args.wave,
                             queue_limit=max(64, args.requests))
    return eng, table, comm


def zipf_trace(args):
    """The bench regime: zipf(1.05) sparse ids folded into the vocab —
    a few hot features dominate, which is what makes the cache pay."""
    rng = np.random.RandomState(42)
    reqs = []
    for _ in range(args.requests):
        raw = rng.zipf(1.05, size=(args.pairs, 26))
        reqs.append(EmbedRequest(
            item_ids=(raw - 1) % args.vocab,
            dense_features=rng.randn(args.pairs, 13).astype(np.float32)))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="wdl", choices=["wdl", "dcn"])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--pairs", type=int, default=2,
                    help="candidate items per request")
    ap.add_argument("--wave", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--embed-dim", type=int, default=8)
    ap.add_argument("--cache-limit", type=int, default=128)
    ap.add_argument("--kill-ps", action="store_true",
                    help="kill the PS for the middle third of the trace")
    args = ap.parse_args()
    # compiled programs persist between runs ($JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache)
    from hetu_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()

    eng, table, comm = build_engine(args)
    reqs = zipf_trace(args)
    third = len(reqs) // 3
    results = {}

    results.update(eng.run(reqs[:third]))            # warm
    if args.kill_ps:
        logger.info("killing the PS mid-trace")
        comm.down = True
    results.update(eng.run(reqs[third:2 * third]))   # (maybe) dark
    if args.kill_ps:
        comm.down = False
        logger.info("PS back up")
    results.update(eng.run(reqs[2 * third:]))        # recovered

    scored = sum(1 for r in results.values()
                 if r.finish_reason == "scored")
    snap = eng.metrics.snapshot()
    cache = table.perf_summary()
    logger.info("scored %d/%d requests, zero loss=%s",
                scored, len(reqs), scored == len(reqs))
    logger.info("cache: hit_rate %.3f, pulled %d rows (%d bytes), "
                "ps_failures %d, stale_served %d, zero_served %d",
                cache["hit_rate"], cache["pulled_rows"],
                cache["pull_bytes"], cache["ps_failures"],
                cache["stale_served_rows"], cache["zero_served_rows"])
    logger.info("latency p50 %.2fms p99 %.2fms, gather p50 %.2fms, "
                "pairs/s %s",
                (snap["latency_p50_s"] or 0) * 1e3,
                (snap["latency_p99_s"] or 0) * 1e3,
                snap["gather_ms_p50"] or 0, snap["pairs_per_sec"])
    tail = eng.metrics.explain_tail()
    if tail:
        logger.info("%s", tail["summary"])
    if args.kill_ps:
        assert cache["ps_failures"] > 0, "the outage never fired"
    return scored / len(reqs)


if __name__ == "__main__":
    frac = main()
    print(f"OK scored_fraction={frac}")
    sys.exit(0 if frac == 1.0 else 1)
