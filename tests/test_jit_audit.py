"""Recompile sentinel (ISSUE 19 satellite): the "ONE compiled core"
claim, asserted instead of hoped.

``analysis/jit_audit.py`` snapshots every registered engine's jit
cache sizes; ``assert_no_recompile`` turns silent steady-state
recompiles (the 320x-regression class: a shape leak re-tracing the
decode core every wave) into a named failure.  ``HETU_VALIDATE=1``
registers every ServingEngine at construction.
"""

import numpy as np
import pytest

from hetu_tpu.analysis import jit_audit
from hetu_tpu.models import GPTConfig
from hetu_tpu.serving import ServingEngine

pytestmark = pytest.mark.smoke

HD = 16


@pytest.fixture(autouse=True)
def _fresh_registry():
    jit_audit.reset()
    yield
    jit_audit.reset()


def _mk_params(seed=0):
    rng = np.random.RandomState(seed)
    p = {"kt_wte_table": rng.randn(61, HD) * 0.05,
         "kt_wpe": rng.randn(32, HD) * 0.05,
         "kt_ln_f_scale": np.ones(HD), "kt_ln_f_bias": np.zeros(HD)}
    for w, shp in [("attn_q", (HD, HD)), ("attn_k", (HD, HD)),
                   ("attn_v", (HD, HD)), ("attn_proj", (HD, HD)),
                   ("ffn_wi", (HD, 4 * HD)), ("ffn_wo", (4 * HD, HD))]:
        p[f"kt_h0_{w}_weight"] = rng.randn(*shp) * 0.05
        p[f"kt_h0_{w}_bias"] = np.zeros(shp[1])
    for ln in ("ln1", "ln2"):
        p[f"kt_h0_{ln}_scale"] = np.ones(HD)
        p[f"kt_h0_{ln}_bias"] = np.zeros(HD)
    return p


_CFG = GPTConfig(vocab_size=61, hidden_size=HD, num_hidden_layers=1,
                 num_attention_heads=2, max_position_embeddings=32,
                 batch_size=1, seq_len=32, dropout_rate=0.0)


def _reqs(rng):
    from hetu_tpu.serving import Request
    return [Request(prompt=list(rng.randint(1, 61, 6)), max_new_tokens=4)
            for _ in range(3)]


def test_fake_engine_cache_growth_raises():
    import jax

    class _Eng:
        pass

    e = _Eng()
    e._name = "fake"
    e._mixed = jax.jit(lambda x: x + 1)
    label = jit_audit.register_engine(e)
    assert label.startswith("fake#")
    e._mixed(np.ones(3, np.float32))
    before = jit_audit.snapshot()
    e._mixed(np.ones(3, np.float32))          # same shape: cached
    jit_audit.assert_no_recompile(before, context="steady wave")
    e._mixed(np.ones(5, np.float32))          # new shape: re-trace
    with pytest.raises(jit_audit.JitAuditError) as ei:
        jit_audit.assert_no_recompile(before, context="shape leak")
    assert "_mixed" in str(ei.value) and "shape leak" in str(ei.value)


def test_dead_engine_drops_out():
    import jax

    class _Eng:
        pass

    e = _Eng()
    e._name = "mortal"
    e._mixed = jax.jit(lambda x: x)
    jit_audit.register_engine(e)
    assert any(lbl.startswith("mortal#")
               for lbl in jit_audit.registered())
    del e
    import gc
    gc.collect()
    assert not any(lbl.startswith("mortal#")
                   for lbl in jit_audit.registered())


def test_engine_steady_state_and_swap_do_not_recompile(monkeypatch):
    """Real engine: HETU_VALIDATE=1 (the suite default) registers it;
    an identical second wave AND a live weight swap reuse every
    compiled core."""
    eng = ServingEngine(_mk_params(), _CFG, slots=2, queue_limit=16,
                        max_seq_len=32)
    assert jit_audit.registered(), \
        "HETU_VALIDATE=1 did not register the engine"
    rng = np.random.RandomState(7)
    first = _reqs(rng)
    eng.run(list(first))
    before = jit_audit.snapshot()
    assert before, "no jit cache sizes visible"
    eng.run(list(first))                       # identical second wave
    jit_audit.assert_no_recompile(before, context="second wave")
    eng.swap_params(_mk_params(seed=1), version=2)
    eng.run(list(first))
    jit_audit.assert_no_recompile(before, context="post-swap wave")
