"""The defaults that took the place of environment knobs (PR 60).

Each class below used to read a ``HETU_*`` knob wherever its argument
was left out.  Built with no such argument, each holds the value the
registry had as that knob's default, stated here as a literal: a default
that moves shows up as a failure, not as a different fleet.
"""

import numpy as np
import pytest

import hetu_tpu as ht  # noqa: F401  (platform forcing + compat shims)
from hetu_tpu.cache import CacheSparseTable
from hetu_tpu.models import GPTConfig
from hetu_tpu.ps.server import PSServer
from hetu_tpu.serving import (
    EmbedServingEngine, FleetAutoscaler, PagedKVManager, ServingEngine,
    ServingRouter, SLO, SLOMonitor, WeightSyncCoordinator,
)

pytestmark = pytest.mark.smoke


def _gpt(name="kd", L=2, H=2, Dh=8, V=61, S=32):
    rng = np.random.RandomState(0)
    hd = H * Dh
    p = {f"{name}_wte_table": rng.randn(V, hd) * 0.05,
         f"{name}_wpe": rng.randn(S, hd) * 0.05,
         f"{name}_ln_f_scale": np.ones(hd),
         f"{name}_ln_f_bias": np.zeros(hd)}
    for i in range(L):
        us = f"{name}_h{i}"
        for w, shp in [("attn_q", (hd, hd)), ("attn_k", (hd, hd)),
                       ("attn_v", (hd, hd)), ("attn_proj", (hd, hd)),
                       ("ffn_wi", (hd, 4 * hd)), ("ffn_wo", (4 * hd, hd))]:
            p[f"{us}_{w}_weight"] = rng.randn(*shp) * 0.05
            p[f"{us}_{w}_bias"] = np.zeros(shp[1])
        for ln in ("ln1", "ln2"):
            p[f"{us}_{ln}_scale"] = np.ones(hd)
            p[f"{us}_{ln}_bias"] = np.zeros(hd)
    cfg = GPTConfig(vocab_size=V, hidden_size=hd, num_hidden_layers=L,
                    num_attention_heads=H, max_position_embeddings=S,
                    batch_size=1, seq_len=S, dropout_rate=0.0)
    return p, cfg


def _engine(**kw):
    p, cfg = _gpt()
    return ServingEngine(p, cfg, slots=2, fast_path=False, **kw)


def _router():
    return ServingRouter(lambda i: _engine(), replicas=1)


def _embed_engine():
    server = PSServer()
    server.param_init("snd_order_embedding", (64, 4), "normal", 0.0, 1.0,
                      seed=3)
    table = CacheSparseTable(limit=16, vocab_size=64, width=4,
                             key="snd_order_embedding", comm=server,
                             policy="LRU")
    rng = np.random.RandomState(0)
    params = {"W1": rng.randn(13, 8), "W2": rng.randn(8, 8),
              "W3": rng.randn(8, 8), "W4": rng.randn(26 * 4 + 8, 1)}
    return EmbedServingEngine(params, {"snd_order_embedding": table})


def _slo_monitor(monkeypatch):
    # the env-declared monitor: the targets are knobs still, the
    # objective and the window are not
    monkeypatch.setenv("HETU_SLO_TTFT_MS", "50")
    monkeypatch.setenv("HETU_SLO_TPS", "10")
    mon = SLOMonitor.from_env()
    assert [s.objective for s in mon.slos] == [0.99, 0.99]
    assert SLO("x", "latency", 1.0).objective == 0.99
    return mon


CASES = {
    "ServingEngine": (
        lambda mp: _engine(spec=2),
        {"chunk": 0, "spec_adapt": True}),
    "PagedKVManager": (
        lambda mp: PagedKVManager(layers=1, heads=2, head_dim=8, slots=2,
                                  max_seq_len=32),
        {"prefix_share": True}),
    "ServingRouter": (
        lambda mp: _router(),
        {"session_affinity": True, "stale": 0.0, "breaker_threshold": 3,
         "breaker_cooldown": 0.5, "retry_limit": 5, "retry_backoff": 0.02,
         "shed_queue": 0.75, "shed_on_slo": True}),
    "PrefixDirectory": (
        lambda mp: _router().directory,      # on by default, no expiry
        {"ttl": 0.0}),
    "FleetAutoscaler": (
        lambda mp: FleetAutoscaler(_router()),
        {"up_burn": 1.0, "up_pressure": 0.75, "up_ticks": 3,
         "down_pressure": 0.15, "down_ticks": 50, "cooldown": 20,
         "warm_prefixes": 4}),
    "WeightSyncCoordinator": (
        lambda mp: WeightSyncCoordinator(_router(), _gpt()[0], 1),
        {"probe_tokens": 4, "drain_steps": 2000, "rollback": True}),
    "EmbedServingEngine": (
        lambda mp: _embed_engine(),
        {"wave": 8, "queue_limit": 64}),
    "SLOMonitor": (
        _slo_monitor,
        {"window": 256}),
}


@pytest.mark.parametrize("holder", sorted(CASES))
def test_defaults_that_replaced_knobs(holder, monkeypatch):
    import os
    for name in [k for k in os.environ if k.startswith("HETU_")]:
        monkeypatch.delenv(name)
    build, want = CASES[holder]
    obj = build(monkeypatch)
    assert type(obj).__name__ == holder
    got = {k: getattr(obj, k) for k in want}
    assert got == want
    assert {k: type(v) for k, v in got.items()} == \
        {k: type(v) for k, v in want.items()}
