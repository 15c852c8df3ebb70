"""Rehearse ``chip_smoke.py`` without the chip (ISSUE 22).

The script's phases are imported and run on the CPU at a tiny width —
wrong paths, arguments and control flow show here, at no chip time — and
``main()`` is held to its contract: with no TPU it exits non-zero before
it trains or serves anything.  The device check is never weakened; what
the phases find on a CPU (interpreted kernels) is not a result.
"""

import json

import jax
import numpy as np
import pytest

import chip_smoke as cs


def tiny(batch):
    # width and depth cut for the CPU; the script itself never narrows
    return cs.gpt2_small(batch, seq_len=64, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         vocab_size=97)


@pytest.fixture(scope="module")
def trained():
    cfg = tiny(2)
    ex, rec = cs.train_phase(cfg, 5, seed=0)
    return cfg, ex, rec


def test_train_phase(trained):
    cfg, ex, rec = trained
    assert rec["steps"] == 5 and len(rec["losses"]) == 5
    assert np.all(np.isfinite(rec["losses"]))
    assert rec["losses"][-1] < rec["losses"][0]
    assert rec["tokens_per_step"] == cfg.batch_size * cfg.seq_len
    assert rec["kernels"] == []       # seq < 1024: no flash, and no chip
    json.dumps(rec)


@pytest.mark.parametrize("tpu_defaults", [False, True],
                         ids=["cpu-defaults", "tpu-defaults-interpreted"])
def test_serve_phase(trained, monkeypatch, tpu_defaults):
    """Every request finishes with ``generate_fast``'s greedy tokens —
    also with the one setting the engine picks differently on a TPU
    (the Pallas kernel in place of the masked reference), here
    interpreted.  The mixed wave over paged KV block 16 is the default
    on both."""
    cfg, ex, _ = trained
    if tpu_defaults:
        monkeypatch.setenv("HETU_SERVE_FAST", "1")
    rec = cs.serve_phase(ex.var_values, cfg, (3, 9, 20, 40), 8, seed=0)
    assert rec["requests"] == 4 and rec["tokens_out"] == 4 * 8
    assert rec["matches_generate_fast"] is True
    assert rec["engine"]["fast_path"] is tpu_defaults
    assert rec["engine"]["paged"] and rec["engine"]["kv_block"] == 16
    json.dumps(rec)


def test_serve_phase_fails_on_a_wrong_token(trained, monkeypatch):
    """The comparison is live: a reference that disagrees raises."""
    from hetu_tpu.models import gpt_decode
    cfg, ex, _ = trained
    real = gpt_decode.generate_fast

    def off_by_one(*a, **kw):
        out = np.array(real(*a, **kw))
        out[0, -1] = (out[0, -1] + 1) % cfg.vocab_size
        return out

    monkeypatch.setattr(gpt_decode, "generate_fast", off_by_one)
    with pytest.raises(RuntimeError, match="differs from generate_fast"):
        cs.serve_phase(ex.var_values, cfg, (5,), 4, seed=0)


def test_multichip_phase_on_four_virtual_devices():
    """The ``--chips 4`` path: dp2 x tp2 against one device, shards on
    four distinct devices."""
    devices = jax.devices()[:4]
    rec = cs.multichip_phase(tiny(4), 3, seed=0, devices=devices)
    assert rec["mesh"] == {"dp": 2, "tp": 2}
    assert len(rec["shard_devices"]) == 4
    np.testing.assert_allclose(rec["losses_dp2_tp2"],
                               rec["losses_one_device"], rtol=2e-2)
    json.dumps(rec)
    with pytest.raises(RuntimeError, match="needs 4 devices"):
        cs.multichip_phase(tiny(4), 3, seed=0, devices=devices[:2])


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_main_refuses_to_run_without_a_tpu(argv, monkeypatch, capsys):
    """No accelerator: non-zero exit, no result line, nothing run."""
    def must_not_run(*a, **kw):
        raise AssertionError("a phase ran without a TPU")
    for phase in ("train_phase", "serve_phase", "multichip_phase"):
        monkeypatch.setattr(cs, phase, must_not_run)
    with pytest.raises(SystemExit) as e:
        cs.main(argv)
    assert e.value.code not in (0, None)
    assert "ok" not in capsys.readouterr().out


def test_pallas_kernels_reads_the_lowered_text():
    text = ('stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = '
            '"_ragged_kernel"} ... @tpu_custom_call {kernel_name = '
            '"_fwd_kernel"}')
    assert cs.pallas_kernels(text) == ["_fwd_kernel", "_ragged_kernel"]
    # an interpreted kernel leaves no custom call, whatever names occur
    assert cs.pallas_kernels('kernel_name = "_fwd_kernel"') == []


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path, no_persistent_compile_cache):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code.  Unset:
    ``<checkout>/.jax_cache`` — never /tmp, a pid or a timestamp.  (The
    real function: under pytest the module's name is a no-op,
    tests/conftest.py.)"""
    import os
    enable_compile_cache = no_persistent_compile_cache
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(os.path.dirname(os.path.abspath(cs.__file__)),
                            ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
