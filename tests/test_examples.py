"""Smoke tests for the example scripts (reference: examples are the
de-facto integration suite; these run the new round-2 ones in-process at
tiny scale)."""

import importlib.util
import os
import sys

import pytest

_EX = os.path.join(os.path.dirname(__file__), "..", "examples")


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_EX, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_main(mod, argv):
    old = sys.argv
    sys.argv = ["prog"] + argv
    try:
        return mod.main()
    finally:
        sys.argv = old


def test_finetune_bert_glue_accuracy_improves():
    mod = _load("nlp/finetune_bert_glue.py", "ex_glue")
    acc = _run_main(mod, ["--num-steps", "25", "--num-layers", "1",
                          "--hidden", "64", "--heads", "2",
                          "--batch-size", "32", "--seq-len", "16",
                          "--eval-every", "25"])
    assert acc > 0.52        # above chance on the learnable synthetic task


def test_gcn_example_generalizes_through_graph():
    mod = _load("gnn/train_gcn.py", "ex_gcn")
    acc = _run_main(mod, ["--nodes", "128", "--epochs", "40",
                          "--mesh", "dp2xtp2"])
    assert acc > 0.9         # held-out nodes classified via propagation


def test_an_examples_main_leaves_the_persistent_cache_off():
    """Every example's ``main()`` asks for the persistent compilation
    cache; under pytest it stays off for the worker's whole life
    (tests/conftest.py, ROADMAP C8 (a))."""
    import jax
    assert not jax.config.jax_compilation_cache_dir
    mod = _load("gnn/train_gcn.py", "ex_gcn_cache")
    _run_main(mod, ["--nodes", "32", "--epochs", "1"])
    assert not jax.config.jax_compilation_cache_dir
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_gcn_hybrid_example_learns_embeddings_on_ps():
    """run_dist_hybrid.py role: PS-served node embeddings + 1.5-D mesh
    compute; structure is the only signal, so held-out accuracy above
    chance proves the hybrid table actually learned."""
    from hetu_tpu.ps.server import PSServer
    import hetu_tpu.ps.client as psc
    PSServer._instance = None
    psc.PSClient._instance = None
    try:
        mod = _load("gnn/train_gcn_hybrid.py", "ex_gcn_hybrid")
        acc = _run_main(mod, ["--nodes", "128", "--epochs", "150",
                              "--learning-rate", "0.4",
                              "--mesh", "dp2xtp2"])
        assert acc > 0.6     # well above the 0.25 chance level
    finally:
        PSServer._instance = None
        psc.PSClient._instance = None


def test_plan_bert_example_runs():
    mod = _load("nlp/plan_bert.py", "ex_plan")
    _run_main(mod, ["--hidden", "32", "--layers", "2", "--heads", "2",
                    "--seq-len", "16", "--vocab", "100",
                    "--global-batch", "16", "--steps", "1"])


def test_plan_gpt_example_runs():
    mod = _load("nlp/plan_gpt.py", "ex_plan_gpt")
    _run_main(mod, ["--hidden", "32", "--layers", "2", "--heads", "2",
                    "--seq-len", "16", "--vocab", "100",
                    "--global-batch", "16", "--steps", "1"])


def test_transformer_mt_learns():
    mod = _load("nlp/train_transformer.py", "ex_mt")
    acc = _run_main(mod, ["--num-steps", "80", "--log-every", "80"])
    assert acc > 0.05    # chance is ~1/62 on the synthetic MT task


def test_long_context_example_tiny():
    mod = _load("nlp/train_long_context.py", "ex_lc")
    toks = _run_main(mod, ["--seq-len", "256", "--tiny"])
    assert toks > 0


def test_gpt_example_learns():
    """Decoder-only causal LM example trains the synthetic next-token
    task to near-zero loss (the loss value is returned via logging;
    re-run the final loss check in-process instead)."""
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=151, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=24,
                    batch_size=4, seq_len=24, dropout_rate=0.0)
    m = GPTForCausalLM(cfg)
    ids = ht.placeholder_op("g_ids")
    labels = ht.placeholder_op("g_labels")
    loss, logits = m(ids, labels=labels)
    train = ht.optim.AdamWOptimizer(learning_rate=3e-3,
                                    weight_decay=0.0).minimize(loss)
    ex = ht.Executor({"train": [loss, train]})
    rng = np.random.RandomState(0)
    first = last = None
    for _ in range(150):
        x = rng.randint(0, 151, (4, 24)).astype(np.int32)
        y = ((3 * x + 7) % 151).astype(np.int32)
        out = ex.run("train", feed_dict={ids: x, labels: y})
        last = float(np.asarray(out[0]))
        first = first if first is not None else last
    assert last < first * 0.5, (first, last)


def test_bert_moe_example_script_runs_on_ep_mesh():
    mod = _load("nlp/train_bert_moe.py", "ex_bert_moe")
    last = _run_main(mod, ["--vocab-size", "97", "--batch-size", "4",
                           "--seq-len", "8", "--num-layers", "2",
                           "--hidden", "32", "--heads", "2",
                           "--num-experts", "4", "--ep", "4", "--dp", "2",
                           "--num-steps", "3"])
    import numpy as np
    assert np.isfinite(last)


def test_gpt_example_script_runs():
    mod = _load("nlp/train_gpt.py", "ex_gpt")
    _run_main(mod, ["--vocab-size", "97", "--batch-size", "2",
                    "--seq-len", "16", "--num-layers", "1",
                    "--num-steps", "3"])


def test_serve_gpt_example_chains_decode():
    """Serving demo: the trained +1 chain decodes correctly through the
    continuous-batching engine for every request in the mixed burst —
    with --spec on (speculative decoding is token-identical by
    construction, so the chain must survive it; the plain engine path
    is pinned by tests/test_serving.py and suite stage 00c)."""
    mod = _load("nlp/serve_gpt.py", "ex_serve")
    frac = _run_main(mod, ["--train-steps", "250", "--requests", "5",
                           "--slots", "2", "--spec", "2"])
    assert frac == 1.0


def test_serve_ctr_example_survives_ps_kill():
    """Embedding serving demo: a zipf CTR trace scores through the
    cache-fronted engine with the PS killed for the middle third —
    every request still scores (stale/zero degradation, zero loss)."""
    mod = _load("ctr/serve_ctr.py", "ex_serve_ctr")
    frac = _run_main(mod, ["--requests", "24", "--wave", "4",
                           "--kill-ps"])
    assert frac == 1.0


def test_gpt_greedy_generation():
    """Inference path: after training next=(x+1)%V, greedy decoding must
    reproduce the arithmetic chain from a prompt (eval subgraph shares
    the trained weights; causal masking makes the padded tail inert)."""
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu.models import GPTConfig, GPTForCausalLM
    from hetu_tpu.models.gpt import greedy_generate

    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=16,
                    batch_size=4, seq_len=16, dropout_rate=0.0)
    m = GPTForCausalLM(cfg)
    ids = ht.placeholder_op("gg_ids")
    labels = ht.placeholder_op("gg_labels")
    loss, _ = m(ids, labels=labels)
    train = ht.optim.AdamOptimizer(learning_rate=3e-3).minimize(loss)
    gen_ids = ht.placeholder_op("gg_gen_ids")
    logits_gen = m(gen_ids)
    ex = ht.Executor({"train": [loss, train], "gen": [logits_gen]})
    rng = np.random.RandomState(1)
    for _ in range(200):
        iv = rng.randint(0, 61, (4, 16)).astype(np.int32)
        lv = ((iv + 1) % 61).astype(np.int32)
        ex.run("train", feed_dict={ids: iv, labels: lv})
    seq = greedy_generate(ex, "gen", gen_ids, 0, [7, 8, 9], 8, 16)
    assert seq == list(range(7, 18)), seq
