"""KV-cached fast decoding (models/gpt_decode.py): one compiled scan
with a preallocated cache must reproduce (a) the graph executor's
full-forward greedy_generate on a trained model and (b) HuggingFace's
generate() on imported weights."""

import numpy as np
import pytest

import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTForCausalLM
from hetu_tpu.models.gpt import greedy_generate
from hetu_tpu.models.gpt_decode import generate_fast


@pytest.fixture(scope="module")
def trained():
    return _trained_model()


# ``python -c _HF_HALF out.npz <prompt tokens>``: a random GPT-2's
# ``state_dict`` and its greedy continuation of the prompt, as numpy
_HF_HALF = """
import sys
import numpy as np
import torch
from transformers import GPT2Config, GPT2LMHeadModel
torch.manual_seed(3)
hf = GPT2LMHeadModel(GPT2Config(
    vocab_size=97, n_embd=32, n_layer=2, n_head=2, n_positions=24,
    resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0)).eval()
with torch.no_grad():
    generated = hf.generate(
        torch.tensor([[int(t) for t in sys.argv[2:]]]), max_new_tokens=10,
        do_sample=False, pad_token_id=0)
np.savez(sys.argv[1], generated=generated.numpy(),
         **{k: v.detach().numpy() for k, v in hf.state_dict().items()})
"""


def _trained_model():
    cfg = GPTConfig(vocab_size=61, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=16,
                    batch_size=4, seq_len=16, dropout_rate=0.0)
    m = GPTForCausalLM(cfg, name="fd")
    ids = ht.placeholder_op("fd_ids")
    labels = ht.placeholder_op("fd_labels")
    loss, _ = m(ids, labels=labels)
    train = ht.optim.AdamOptimizer(learning_rate=3e-3).minimize(loss)
    gen_ids = ht.placeholder_op("fd_gen_ids")
    logits_gen = m(gen_ids)
    ex = ht.Executor({"train": [loss, train], "gen": [logits_gen]})
    rng = np.random.RandomState(1)
    for _ in range(200):
        iv = rng.randint(0, 61, (4, 16)).astype(np.int32)
        lv = ((iv + 1) % 61).astype(np.int32)
        ex.run("train", feed_dict={ids: iv, labels: lv})
    return cfg, ex, gen_ids


class TestFastDecode:
    def test_matches_graph_greedy_generate(self, trained):
        """Same trained weights: the KV-cached scan and the per-token
        full-forward path must emit the identical greedy sequence."""
        cfg, ex, gen_ids = trained
        slow = greedy_generate(ex, "gen", gen_ids, 0, [7, 8, 9], 8, 16)
        cfg1 = GPTConfig(vocab_size=61, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         max_position_embeddings=16, batch_size=1,
                         seq_len=16, dropout_rate=0.0)
        fast = generate_fast(ex.var_values, cfg1, [7, 8, 9],
                             num_tokens=8)
        assert fast[0].tolist() == slow
        # the trained arithmetic chain actually decoded
        assert slow == list(range(7, 18))

    def test_matches_hf_generate_on_imported_weights(self, tmp_path):
        """torch's half (the HF model, its weights, ``hf.generate``'s
        tokens) runs in a CHILD process: a worker that had imported
        torch died at its next JAX compile (ROADMAP C8 (a))."""
        import importlib.util
        import subprocess
        import sys
        for mod in ("torch", "transformers"):
            if importlib.util.find_spec(mod) is None:
                pytest.skip(f"could not import {mod!r}")
        prompt = [5, 11, 17]
        out = tmp_path / "hf.npz"
        subprocess.run([sys.executable, "-c", _HF_HALF, str(out),
                        *map(str, prompt)], check=True, timeout=300)
        hf = np.load(out)
        cfg = GPTConfig(vocab_size=97, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=2,
                        max_position_embeddings=24, batch_size=1,
                        seq_len=24, dropout_rate=0.0)
        params = ht.hf.convert_gpt2(
            {k: hf[k] for k in hf.files if k != "generated"},
            prefix="transformer.")
        ours = generate_fast(params, cfg, prompt, num_tokens=10)
        assert ours[0].tolist() == hf["generated"][0].tolist()

    def test_sampling_contract(self, trained):
        cfg, ex, _ = trained
        cfg1 = GPTConfig(vocab_size=61, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         max_position_embeddings=16, batch_size=1,
                         seq_len=16, dropout_rate=0.0)
        params = ex.var_values
        a = generate_fast(params, cfg1, [3, 4], num_tokens=6,
                          temperature=0.8, top_k=4, seed=7)
        b = generate_fast(params, cfg1, [3, 4], num_tokens=6,
                          temperature=0.8, top_k=4, seed=7)
        c = generate_fast(params, cfg1, [3, 4], num_tokens=6,
                          temperature=0.8, top_k=4, seed=8)
        np.testing.assert_array_equal(a, b)       # seed-deterministic
        assert a.shape == (1, 8)
        assert a.max() < 61 and a.min() >= 0
        assert (a[0, :2] == [3, 4]).all()         # prompt preserved
        assert not np.array_equal(a, c) or True   # different seed free

    def test_batched_prompts(self, trained):
        cfg, ex, _ = trained
        cfg2 = GPTConfig(vocab_size=61, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         max_position_embeddings=16, batch_size=2,
                         seq_len=16, dropout_rate=0.0)
        out = generate_fast(ex.var_values, cfg2,
                            [[7, 8, 9], [20, 21, 22]],
                            num_tokens=6)
        assert out[0].tolist() == list(range(7, 16))
        assert out[1].tolist() == list(range(20, 29))

    def test_eos_stops_generation(self, trained):
        """eos_id regression: on the trained +1 chain [7,8,9] -> 10,11,
        12,... an eos_id of 12 must emit 10,11,12 then pad the rest of
        the requested span (shape contract unchanged); rows that never
        sample EOS run the full span as before."""
        cfg, ex, _ = trained
        cfg2 = GPTConfig(vocab_size=61, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         max_position_embeddings=16, batch_size=2,
                         seq_len=16, dropout_rate=0.0)
        out = generate_fast(ex.var_values, cfg2,
                            [[7, 8, 9], [20, 21, 22]], num_tokens=6,
                            eos_id=12, pad_id=0)
        # row 0 hits EOS after 3 generated tokens; pad after
        assert out[0].tolist() == [7, 8, 9, 10, 11, 12, 0, 0, 0]
        # row 1 never samples 12 inside its span: untouched
        assert out[1].tolist() == list(range(20, 29))
        # eos only triggers PAST the prompt: a 12 inside the prompt is
        # teacher-forced context, not a stop
        out2 = generate_fast(ex.var_values, cfg2,
                             [[11, 12, 13], [30, 31, 32]], num_tokens=4,
                             eos_id=12, pad_id=0)
        assert out2[0].tolist() == [11, 12, 13, 14, 15, 16, 17]
        # custom pad_id lands in the padded tail
        out3 = generate_fast(ex.var_values, cfg2,
                             [[7, 8, 9], [7, 8, 9]], num_tokens=6,
                             eos_id=10, pad_id=59)
        assert out3[0].tolist() == [7, 8, 9, 10, 59, 59, 59, 59, 59]

    def test_overlong_request_raises(self, trained):
        cfg, ex, _ = trained
        with pytest.raises(ValueError):
            generate_fast(ex.var_values, cfg, [1, 2], num_tokens=100)
        with pytest.raises(ValueError):
            generate_fast(ex.var_values, cfg, [], num_tokens=4)
        with pytest.raises(ValueError):
            generate_fast(ex.var_values, cfg, [1, 2], num_tokens=0)


class TestTensorParallelDecode:
    """Multi-chip serving: tp_shard_params places the weights Megatron-
    style and GSPMD propagates the split through the whole decode scan —
    the sharded run must emit the identical greedy sequence."""

    def test_tp4_matches_unsharded(self):
        from hetu_tpu.models.gpt_decode import tp_shard_params
        from hetu_tpu.parallel.mesh import make_mesh
        cfg = GPTConfig(vocab_size=61, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        max_position_embeddings=16, batch_size=4,
                        seq_len=16, dropout_rate=0.0)
        m = GPTForCausalLM(cfg, name="tq")
        ids = ht.placeholder_op("tq_ids")
        labels = ht.placeholder_op("tq_labels")
        loss, _ = m(ids, labels=labels)
        train = ht.optim.AdamOptimizer(learning_rate=3e-3).minimize(loss)
        ex = ht.Executor({"train": [loss, train]})
        rng = np.random.RandomState(1)
        for _ in range(150):
            iv = rng.randint(0, 61, (4, 16)).astype(np.int32)
            ex.run("train", feed_dict={
                ids: iv, labels: ((iv + 1) % 61).astype(np.int32)})
        base = generate_fast(ex.var_values, cfg, [7, 8, 9],
                             num_tokens=6)
        mesh = make_mesh({"tp": 4})
        sharded = tp_shard_params(ex.var_values, mesh, cfg)
        # the placed weights really are split over tp
        w = sharded["tq_h0_attn_q_weight"]
        assert {s.data.shape for s in w.addressable_shards} == {(32, 8)}
        out = generate_fast(sharded, cfg, [7, 8, 9], num_tokens=6)
        assert out[0].tolist() == base[0].tolist()
        assert out[0].tolist() == list(range(7, 16))

    def test_head_divisibility_guard(self):
        from hetu_tpu.models.gpt_decode import tp_shard_params
        from hetu_tpu.parallel.mesh import make_mesh
        cfg = GPTConfig(vocab_size=61, hidden_size=30,
                        num_hidden_layers=1, num_attention_heads=3,
                        max_position_embeddings=8, batch_size=1,
                        seq_len=8, dropout_rate=0.0)
        mesh = make_mesh({"tp": 4})
        with pytest.raises(ValueError):
            tp_shard_params({"g_wte_table": np.zeros((61, 30))},
                            mesh, cfg)


def test_prep_param_preserves_sharding():
    """Regression pin for the silent-TP-kill bug: a NamedSharding placed
    by tp_shard_params must SURVIVE generate_fast's param prep (an
    np.asarray round-trip would re-place it replicated)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from hetu_tpu.models.gpt_decode import _prep_param
    from hetu_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"tp": 4})
    arr = jax.device_put(np.ones((8, 16), np.float32),
                         NamedSharding(mesh, P(None, "tp")))
    out = _prep_param(arr)
    assert out is arr                       # untouched, placement intact
    assert isinstance(out.sharding, NamedSharding)
    assert out.sharding.spec == P(None, "tp")
    # non-jax inputs still land as f32 jax arrays
    out2 = _prep_param(np.ones((4,), np.float64))
    assert out2.dtype == jnp.float32


def test_bf16_decode_matches_f32_greedy(trained):
    """dtype=bfloat16 halves weights + KV cache (LN statistics stay
    f32); on the near-deterministic trained chain the greedy sequence
    is unchanged — the f32 sequence is already pinned to the same
    literal by test_matches_graph_greedy_generate."""
    cfg, ex, _ = trained
    cfg1 = GPTConfig(vocab_size=61, hidden_size=32,
                     num_hidden_layers=2, num_attention_heads=2,
                     max_position_embeddings=16, batch_size=1,
                     seq_len=16, dropout_rate=0.0)
    bf16 = generate_fast(ex.var_values, cfg1, [7, 8, 9], num_tokens=6,
                         dtype=jnp.bfloat16)
    assert bf16[0].tolist() == list(range(7, 16))
