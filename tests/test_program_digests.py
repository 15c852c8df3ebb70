"""Every accepted program's lowered text, a case a program.

A PR that is meant to change no behaviour is judged here: each entry of
``PARENT`` is sha256[:16] of ``serve_mixed_paged_fn(...).lower(...)
.as_text()`` for one small configuration of an accepted cell's block
spec, one q-block bucket and one ``has_fresh``.  A family is lowered
once (module-scoped ``digests``), by the builder beside the spec's own
tests; a program that moved fails under its own name.  A PR that
changes what a wave computes changes its entries on purpose and says
which operation differs.

``masked`` families are the ``jax.numpy`` path, lowered for the CPU.
``kernels`` families are lowered for the described v5e chip with the
Pallas kernels not interpreted (``attn="ragged"``), each Mosaic
kernel's serialized body replaced by its assembly WITHOUT locations
(``strip_kernel_locations``: the bytecode carries file names and line
numbers, which move whenever a line is added above a kernel).

``wave_programs``' latent Q 32 pair has 4 slots x 32 = 128 rows, under
the packed wave's floor of 256 (``gd.wave_rows``), so it stays padded
and takes ``ragged_paged_mla``: it does NOT cover PR 46's packed chunk
program.  That one is the ``PACKED`` families' (16 slots x 32 packed
into 256 rows), whose latent kernel call is ``ragged_paged_mla_rows``
(named ``ragged_paged_mla`` in the text too; its result is
``[256 * heads, kv_lora_rank]``).
"""

import jax
import pytest

# the described v5e chip and its shape-with-sharding factory
from test_chip_compile import (  # noqa: E402,F401
    no_compile_cache, sds, strip_kernel_locations, topo)
from test_hybrid_moe import digest, wave_programs
from test_kda_gqa import kda_gqa_programs
from test_kda_latent import kda_latent_programs
from test_nemotron_h import nemotron_programs
from test_parallel_moe import parallel_moe_programs
from test_retention import retention_programs, window_programs
from test_sparse_latent import sparse_latent_programs
from test_window_moe import falcon_case, hybrid_programs, lower_cases


def packed_programs(sds, attn):
    """The GPT-2 and latent chunk programs of 16 slots: packed rows."""
    return {k: low for k, low in wave_programs(sds, attn, slots=16).items()
            if k.endswith("Q32.fresh1")}


def packed_rows_programs(sds, attn):
    """The grouped-query (``lfm2``, ``falcon``) and sliding-window
    (``mellum2``) chunk programs of 16 slots: 16 x 32 packed into 256
    rows, whose K/V rows kernel takes the packed rows (ISSUE 54)."""
    out = hybrid_programs(sds, attn, slots=16, qs=(32,))
    out.update(window_programs(sds, attn, qs=(32,), slots=16))
    return {k: low for k, low in out.items() if k.endswith("fresh1")}


def ssm_kernel_programs(sds):
    """The small ``nemotron_h`` and ``falcon_h1`` models with a state of
    128 columns: every program's one-row slots through ``ssm_step``."""
    out = nemotron_programs(sds, "ragged", ssm_state=128)
    out.update(lower_cases(sds, "ragged", {"falcon": falcon_case(
        sds, 4, 33, 16, ssm_state=128)}, 4, 8))
    return out


def train_programs(_sds):
    """A two-layer GPT train step as the executor jits it (flash on, a head
    pair a lane block, bf16 mixed precision, AdamW), lowered for the CPU
    with the flash kernels interpreted: the training cell's program in
    small."""
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu.executor import gather_feeds
    from hetu_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                    num_attention_heads=2, max_position_embeddings=256,
                    dropout_rate=0.0, batch_size=2, seq_len=256,
                    use_flash=True)
    ids = ht.placeholder_op("pd_gpt_ids")
    labels = ht.placeholder_op("pd_gpt_labels")
    loss, _ = GPTForCausalLM(cfg, name="pd_gpt")(ids, labels=labels)
    opt = ht.optim.AdamWOptimizer(learning_rate=3e-4)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     mixed_precision="bf16", seed=0)
    x = np.zeros((2, 256), np.int32)
    sub = ex.subexecutor["train"]
    feeds = gather_feeds(sub, {ids: x, labels: x}, peek=True)
    sig = tuple(sorted((k, tuple(v.shape), str(v.dtype))
                       for k, v in feeds.items()))
    return {"gpt.S256.flash": jax.jit(sub._compile(sig)).lower(
        ex.var_values, ex.opt_states, ex.step, ex.rng, feeds)}


# family: (builder, lowered with the kernels for the described chip)
FAMILIES = {
    "TRAIN_FLASH": (train_programs, False),
    "PARENT_MASKED": (lambda s: wave_programs(s, "masked"), False),
    "PARENT_RAGGED": (lambda s: wave_programs(s, "ragged"), True),
    "PARENT_HYBRID_MASKED": (lambda s: hybrid_programs(s, "masked"), False),
    "PARENT_HYBRID_RAGGED": (lambda s: hybrid_programs(s, "ragged"), True),
    "PARENT_WINDOW_MASKED": (window_programs, False),
    "PARENT_WINDOW_RAGGED": (
        lambda s: window_programs(s, "ragged", qs=(1, 32)), True),
    "PARENT_RETENTION_MASKED": (retention_programs, False),
    "PARENT_PACKED_MASKED": (lambda s: packed_programs(s, "masked"), False),
    "PARENT_PACKED_RAGGED": (lambda s: packed_programs(s, "ragged"), True),
    "PACKED_ROWS_MASKED": (
        lambda s: packed_rows_programs(s, "masked"), False),
    "PACKED_ROWS_RAGGED": (lambda s: packed_rows_programs(s, "ragged"), True),
    "NEMOTRON_MASKED": (lambda s: nemotron_programs(s, "masked"), False),
    "NEMOTRON_RAGGED": (lambda s: nemotron_programs(s, "ragged"), True),
    "NEMOTRON_DECODE_TILES_RAGGED": (
        lambda s: nemotron_programs(s, "ragged", qs=(1,), slots=32), True),
    "SSM_STEP_RAGGED": (ssm_kernel_programs, True),
    "SPARSE_LATENT_MASKED": (
        lambda s: sparse_latent_programs(s, "masked"), False),
    "SPARSE_LATENT_RAGGED": (
        lambda s: sparse_latent_programs(s, "ragged"), True),
    "PARALLEL_MOE_MASKED": (
        lambda s: parallel_moe_programs(s, "masked"), False),
    "PARALLEL_MOE_RAGGED": (
        lambda s: parallel_moe_programs(s, "ragged"), True),
    "PARALLEL_MOE_PACKED_RAGGED": (
        lambda s: {k: low for k, low in parallel_moe_programs(
            s, "ragged", qs=(32,), slots=16).items()
            if k.endswith("fresh1")}, True),
    "KDA_LATENT_MASKED": (lambda s: kda_latent_programs(s, "masked"), False),
    "KDA_LATENT_RAGGED": (lambda s: kda_latent_programs(s, "ragged"), True),
    "KDA_GQA_MASKED": (lambda s: kda_gqa_programs(s, "masked"), False),
    "KDA_GQA_RAGGED": (lambda s: kda_gqa_programs(s, "ragged"), True),
}

PARENT = {
    # PR 61 pins the TRAINING program here for the first time, and changes
    # it on purpose: the parent of PR 61 (commit f142327) lowers this same
    # builder to gpt.S256.flash 7a4312651f516abf.  What differs, a layer:
    # ``flash_attention`` took q, k, v transposed to ``[B*H, S, D]`` through
    # three kernels (``flash_fwd``; ``flash_bwd_dkv`` and ``flash_bwd_dq``
    # after an XLA reduction for delta) and transposed the output and the
    # three gradients back; it now hands the ``[B, S, H*D]`` views to TWO
    # kernels under ``jit`` (``flash_fwd``; ``flash_bwd_dkv``, which gives
    # dq, dk and dv and computes delta inside) that work on the transposed
    # scores, and no ``transpose`` of a ``[B, S, H, D]`` operand is left.
    # Every serving entry below is untouched.
    "TRAIN_FLASH": {
        "gpt.S256.flash": "7a891d8317ba08be"},
    # PR 56 changed, on purpose, every entry whose q-block is a page or more
    # wide (Q 32, and Q 8 where the block is 8) over a float K/V pool,
    # ``fresh0`` and ``fresh1``, 37 in all: the ONE operation that differs
    # is the K/V write under ``kv_write``, which was ``_kv_write_pages`` (a
    # pad, a shifted slice under ``vmap``, a gather of ``B x K`` pages, a
    # select and a scatter, once for K and once for V, on k and v unpacked
    # to ``[B, Q]`` in a packed program) and is the ``pallas_call``
    # ``paged_kv_write`` (``kernels/paged_kv_write.py``: K and V in one call
    # a layer on the rows as they lie, after ``touched_pages``' list once a
    # table; interpreted in the masked families, Mosaic in the kernel ones);
    # in the MASKED packed programs k and v still unpack, under
    # ``attention`` now, for the chunk's own fresh K/V.  Every Q 1 entry,
    # and every latent, sparse-latent, retention and training entry, is the
    # parent's.  The parent of PR 56 (commit dcdc3e4) lowered the changed
    # ones to: PARENT_MASKED: gpt2.Q32.fresh0 fe44f8933c85a0a3,
    # gpt2.Q32.fresh1 910e84f83fe7e6d4; PARENT_RAGGED: gpt2.Q32.fresh0
    # a7c64ccb01632823, gpt2.Q32.fresh1 a7c64ccb01632823;
    # PARENT_HYBRID_MASKED: lfm2.Q32.fresh0 34501c1582f67ac1,
    # lfm2.Q32.fresh1 90e43d41275f0717, falcon.Q32.fresh0 7a593dcee24143b8,
    # falcon.Q32.fresh1 e1c7e7369a2d3dbb; PARENT_HYBRID_RAGGED:
    # lfm2.Q32.fresh0 4305fadbb584f1c8, lfm2.Q32.fresh1 4305fadbb584f1c8,
    # falcon.Q32.fresh0 c49aeded6dc2f4d8, falcon.Q32.fresh1
    # c49aeded6dc2f4d8; PARENT_WINDOW_MASKED: mellum2.Q8.fresh0
    # 4e448bc7c3ca2927, mellum2.Q8.fresh1 293ab0d1cdcca346;
    # PARENT_WINDOW_RAGGED: mellum2.Q32.fresh0 57d8e12e8808ce77,
    # mellum2.Q32.fresh1 57d8e12e8808ce77; PARENT_PACKED_MASKED:
    # gpt2.Q32.fresh1 6fd67af84c39d578; PARENT_PACKED_RAGGED:
    # gpt2.Q32.fresh1 d56b6d40bd76fb37; PACKED_ROWS_MASKED: lfm2.Q32.fresh1
    # af661b9170a114df, falcon.Q32.fresh1 e3194d430ad60fda,
    # mellum2.Q32.fresh1 186e8bc80e57df70; PACKED_ROWS_RAGGED:
    # lfm2.Q32.fresh1 15d4407705d449f8, falcon.Q32.fresh1 3515c214ca1f81d5,
    # mellum2.Q32.fresh1 847f077464895fb5; NEMOTRON_MASKED:
    # nemotron.Q32.fresh0 7a29ba66835c6b6a, nemotron.Q32.fresh1
    # 01313047839bb51b; NEMOTRON_RAGGED: nemotron.Q32.fresh0
    # d6103b62da292653, nemotron.Q32.fresh1 d6103b62da292653;
    # SSM_STEP_RAGGED: nemotron.Q32.fresh0 453c8f1ffd43c2f9,
    # nemotron.Q32.fresh1 453c8f1ffd43c2f9, falcon.Q32.fresh0
    # 87ef4a527badb7db, falcon.Q32.fresh1 87ef4a527badb7db;
    # PARALLEL_MOE_MASKED: parallel_moe.Q32.fresh0 b116b5a760b749bb,
    # parallel_moe.Q32.fresh1 fcb5ecd9d4365e95; PARALLEL_MOE_RAGGED:
    # parallel_moe.Q32.fresh0 525e260c074e3c7d, parallel_moe.Q32.fresh1
    # 525e260c074e3c7d; PARALLEL_MOE_PACKED_RAGGED: parallel_moe.Q32.fresh1
    # 7c048e6a6959d14d.
    # GPT-2 (2 layers, 4 heads of 64, bf16) and the latent block, as the
    # PARENT of PR 34 lowered them (commit cdadf90).  PR 41 changed the
    # latent Q 32 pair on purpose: 128 rows x top-2 over 8 experts are 32
    # expected rows a group, where ``moe_decode.takes_kernel`` hands the
    # routed experts' products to ``kernels/grouped_matmul`` (here its
    # interpreted body; parent of PR 41: 6d630c5c240e04a8).  The Q 1
    # pair, 8 assignment rows, keeps ``ragged_dot`` and the parent's text.
    "PARENT_MASKED": {
        "gpt2.Q1.fresh0": "9691db83be028caf",
        "gpt2.Q1.fresh1": "7beca803d1ca3f4f",
        "gpt2.Q32.fresh0": "5de13c824f077dfb",
        "gpt2.Q32.fresh1": "280302dbfcf81e0a",
        "latent.Q1.fresh0": "7af25cbea0694a84",
        "latent.Q1.fresh1": "7af25cbea0694a84",
        "latent.Q32.fresh0": "57bb0151f876695a",
        "latent.Q32.fresh1": "57bb0151f876695a"},
    # The latent Q 32 pair is PR 43's: a q-tile of 32 queries is taller
    # than the latent kernel's short height (8 queries), so the kernel of
    # a chunk program holds its step at two heights, ``ragged_attention.
    # tile_heights``; the parent of PR 43 lowered it to c873bfc54690610f,
    # and before PR 41's grouped kernel to 9be25ac3fb1f17f1.  The GPT-2
    # programs are the parent's at every Q: one query head a K/V head
    # stacks too few rows for a second height, ``_SHORT_MIN_ROWS``.
    "PARENT_RAGGED": {
        "gpt2.Q1.fresh0": "5919310cf2517705",
        "gpt2.Q1.fresh1": "5919310cf2517705",
        "gpt2.Q32.fresh0": "ac15699e0f20c049",
        "gpt2.Q32.fresh1": "ac15699e0f20c049",
        "latent.Q1.fresh0": "3e2ddf60b91b860d",
        "latent.Q1.fresh1": "3e2ddf60b91b860d",
        "latent.Q32.fresh0": "eef22714386f596c",
        "latent.Q32.fresh1": "eef22714386f596c"},
    # A small lfm2_moe and a small falcon_h1 configuration, as the PARENT
    # of PR 42 lowered them (commit c2d3500).
    "PARENT_HYBRID_MASKED": {
        "lfm2.Q1.fresh0": "d5335fb3237bcd18",
        "lfm2.Q1.fresh1": "43b61d3f0c614ec1",
        "lfm2.Q32.fresh0": "c46894dad3a30ac4",
        "lfm2.Q32.fresh1": "dfb682136f835490",
        "falcon.Q1.fresh0": "5ed3ea5756c9fb13",
        "falcon.Q1.fresh1": "471086ba72e5dab2",
        "falcon.Q32.fresh0": "3d63c3dc685e1893",
        "falcon.Q32.fresh1": "f04b1a8499c041a8"},
    # The four Q 32 entries (4 slots x 32 rows stay padded: the DENSE
    # entry) are the PARENT of PR 43's again: PR 43 gave the rows kernel
    # of a chunk program a second height (1c3dd14502830c9d and
    # 0aee2c839734b68c), and PR 54, whose packed entry takes every chunk
    # wave of 16 slots or more with its heights, took it out of the dense
    # kernel (``_kv_rows_kernel`` scores every live tile whole; what
    # differs is the kernel's body alone: no second ``scf.if`` branch of
    # 8 or 16 queries, no ``tile_heights`` of the prefetched ``q_len``).
    # No ``ragged_paged_window`` in any of them.
    "PARENT_HYBRID_RAGGED": {
        "lfm2.Q1.fresh0": "d8e968f0ef99f889",
        "lfm2.Q1.fresh1": "d8e968f0ef99f889",
        "lfm2.Q32.fresh0": "a7d43a2b7b33081d",
        "lfm2.Q32.fresh1": "a7d43a2b7b33081d",
        "falcon.Q1.fresh0": "b452b65dabd73846",
        "falcon.Q1.fresh1": "b452b65dabd73846",
        "falcon.Q32.fresh0": "f310f336e0d1e441",
        "falcon.Q32.fresh1": "f310f336e0d1e441"},
    # tests/test_window_moe.py's small sliding-window / full model, as
    # the PARENT of PR 44 lowered it (commit 56a5ee3).
    "PARENT_WINDOW_MASKED": {
        "mellum2.Q1.fresh0": "353f217c54a48780",
        "mellum2.Q1.fresh1": "6c5a3286d660bb18",
        "mellum2.Q8.fresh0": "968f72e606dea2ed",
        "mellum2.Q8.fresh1": "535d5dc67edf3c98"},
    # The families below were taken on the PARENT of PR 47 (commit
    # 2accd4a, in a scratch checkout, before that PR's first deletion).
    # But every Q 32 entry of a K/V rows kernel with several query heads
    # a K/V head, which is PR 54's: the dense kernel's one height (see
    # ``PARENT_HYBRID_RAGGED``; the body of ``ragged_paged_mixed`` /
    # ``ragged_paged_window`` alone differs).  The parent of PR 54
    # lowered them to: mellum2 598db0ab0ecb210c, nemotron
    # 7f0e176ec1f6c772, and in ``SSM_STEP_RAGGED`` nemotron
    # 69fe6c6b2ab88112, falcon 7209be57d34aca4a.
    "PARENT_WINDOW_RAGGED": {
        "mellum2.Q1.fresh0": "071a841212bd9ec1",
        "mellum2.Q1.fresh1": "071a841212bd9ec1",
        "mellum2.Q32.fresh0": "c8ec1503c38d1cdf",
        "mellum2.Q32.fresh1": "c8ec1503c38d1cdf"},
    "PARENT_RETENTION_MASKED": {
        "brumby.Q1.fresh0": "e33a3369dfd0dd1b",
        "brumby.Q1.fresh1": "e33a3369dfd0dd1b",
        "brumby.Q32.fresh0": "addbf0ad5c1461c1",
        "brumby.Q32.fresh1": "addbf0ad5c1461c1"},
    "PARENT_PACKED_MASKED": {
        "gpt2.Q32.fresh1": "2106c857b9db5a61",
        "latent.Q32.fresh1": "9b275df0e7c91882"},
    # The GPT-2 entry is PR 54's: the K/V rows kernel is handed the 256
    # packed rows as they lie (``ragged_paged_attention_rows``: its call's
    # operand and result are ``[256, 256]`` rows where the parent's were
    # ``[16, 32, 256]``, the grid ``(row tile)`` with the slots' visits
    # inside, and the query's ``rows.unpack`` gather and the result's
    # ``rows.pack`` gather are gone; k and v unpacked for the page write
    # until PR 56); the parent of PR 54 lowered it to ee10736bb0265878.  The
    # latent entry is the parent's.
    "PARENT_PACKED_RAGGED": {
        "gpt2.Q32.fresh1": "81bdfb51db67cfc7",
        "latent.Q32.fresh1": "89ef03c3aad79ffc"},
    # The grouped-query and sliding-window chunk programs of 16 slots
    # (``packed_rows_programs``).  MASKED: as the PARENT of PR 54 lowered
    # them (commit 2bf7f3c; the masked path still unpacks q and packs o).
    # RAGGED: PR 54's own: every pool and window layer's kernel
    # (``ragged_paged_mixed``, ``ragged_paged_window``) takes the packed
    # rows, window by window the members' rows; the parent lowered them
    # to cfece650ee5bc240, 41c74b76a0765db4 and 6a71e6743f415082 (q
    # unpacked to ``[16, 32, H, Dh]``, relaid by ``_grouped_rows``, the
    # dense grid ``(slot, q-tile)``, the result relaid and packed).
    "PACKED_ROWS_MASKED": {
        "lfm2.Q32.fresh1": "c6972a63bc27aa90",
        "falcon.Q32.fresh1": "91e5679c51633f9e",
        "mellum2.Q32.fresh1": "bc774e50bedcf550"},
    "PACKED_ROWS_RAGGED": {
        "lfm2.Q32.fresh1": "1b926479d35eedc5",
        "falcon.Q32.fresh1": "2302f4d7ee7f3f4b",
        "mellum2.Q32.fresh1": "c64ceea853768c1d"},
    # PR 48's own, no parent's: tests/test_nemotron_h.py's small
    # ``nemotron_h`` model (eleven one-part layers, positions "none",
    # expert layers that hold experts [4, 8) of 16 at a latent width, the
    # squared-ReLU experts).  4 slots x 32 rows x top-4 are 512 sorted
    # rows of which 128 can land on 4 held experts, 32 a group: the Q 32
    # pair takes ``kernels/grouped_matmul`` with the ``relu2`` epilogue;
    # the Q 1 pair, 16 sorted rows, keeps ``ragged_dot``.
    "NEMOTRON_MASKED": {
        "nemotron.Q1.fresh0": "c1af25b0c15abce7",
        "nemotron.Q1.fresh1": "d3a20ecdcb55212b",
        "nemotron.Q32.fresh0": "e498085023f16a50",
        "nemotron.Q32.fresh1": "1e3d7c6b8b8996b6"},
    "NEMOTRON_RAGGED": {
        "nemotron.Q1.fresh0": "acb5db193cd96995",
        "nemotron.Q1.fresh1": "acb5db193cd96995",
        "nemotron.Q32.fresh0": "c894fccb98518524",
        "nemotron.Q32.fresh1": "c894fccb98518524"},
    # PR 49's own, no parent's: the same model at 32 slots, whose DECODE
    # wave's 32 x top-4 sorted rows are one whole row tile (32 can land
    # on the 4 held experts): since PR 49 the rule hands such a wave's
    # products to ``kernels/grouped_matmul`` too (two lowerings, ``relu2``
    # up and down, for the five expert layers) and the text holds no
    # ``ragged_dot``.  Every Q 1 program above has 8 or 16 sorted rows,
    # no whole tile, and keeps ``ragged_dot`` and its parent's text.
    "NEMOTRON_DECODE_TILES_RAGGED": {
        "nemotron.Q1.fresh0": "3153082ce74992be",
        "nemotron.Q1.fresh1": "3153082ce74992be"},
    # PR 50's own, no parent's: the small ``nemotron_h`` and ``falcon_h1``
    # models above with a state of 128 columns (every entry above has
    # 16), where ``ssm_decode.takes_kernel`` hands the one-row slots'
    # step to ``kernels/ssm_step``: ONE lowering a program for all of a
    # model's mixers (five and two), in the decode program and in the
    # chunk bucket's, whose wide slots keep the chunked form's
    # ``while``; no ``multiply_add`` over the whole state is left.
    "SSM_STEP_RAGGED": {
        "nemotron.Q1.fresh0": "c3a7775fd1b7f6c8",
        "nemotron.Q1.fresh1": "c3a7775fd1b7f6c8",
        "nemotron.Q32.fresh0": "7e212d754c5f7b0d",
        "nemotron.Q32.fresh1": "7e212d754c5f7b0d",
        "falcon.Q1.fresh0": "737fe178722df78a",
        "falcon.Q1.fresh1": "737fe178722df78a",
        "falcon.Q32.fresh0": "ed80456adcc6e497",
        "falcon.Q32.fresh1": "ed80456adcc6e497"},
    # PR 51's own, no parent's: tests/test_sparse_latent.py's small
    # five-layer model (latent operators by layer: two full layers with
    # an indexer of top 16, three window layers over a latent ring of
    # their own width, the gate, the rescale, 2 of 8 experts held).  4
    # slots x 32 rows stay padded, so the window layers take the dense
    # entry ``ragged_paged_mla`` under ``window`` (named
    # ``ragged_paged_mla_window``); the full layers walk their pages
    # under the chosen rows' mask in either program
    # (``ragged_paged_mla_rows(allowed=)``, ``ragged_paged_mla_sparse``);
    # a latent wave has no fresh variant, so a bucket's two programs are
    # one text.
    "SPARSE_LATENT_MASKED": {
        "sparse_latent.Q1.fresh0": "1176acbbb3579b74",
        "sparse_latent.Q1.fresh1": "1176acbbb3579b74",
        "sparse_latent.Q32.fresh0": "3e54814565ed48e7",
        "sparse_latent.Q32.fresh1": "3e54814565ed48e7"},
    "SPARSE_LATENT_RAGGED": {
        "sparse_latent.Q1.fresh0": "47efd75d5a24e418",
        "sparse_latent.Q1.fresh1": "47efd75d5a24e418",
        "sparse_latent.Q32.fresh0": "984b1ff568454a30",
        "sparse_latent.Q32.fresh1": "984b1ff568454a30"},
    # PR 55's own, no parent's: tests/test_parallel_moe.py's small period
    # of four (the ``cohere2_moe`` family: a PARALLEL block on one
    # bias-free LayerNorm under the scope ``par_norm``, three sliding
    # layers rotated over a ring and one full layer that rotates
    # nothing, 32 query heads over 2: group 16, a bias-free sigmoid
    # router over 2 of 8 experts held, four shared experts averaged, a
    # tied head).  4 slots x 32 rows stay padded and take the dense entry
    # of the rows kernel; 16 slots x 32 are packed into 256 rows and take
    # its packed entry (``ragged_paged_attention_rows``); the Q 1 pair's
    # 12 sorted rows keep ``ragged_dot``.
    "PARALLEL_MOE_MASKED": {
        "parallel_moe.Q1.fresh0": "53e460fb51d02684",
        "parallel_moe.Q1.fresh1": "4246d5f557aa5890",
        "parallel_moe.Q32.fresh0": "6c2d876f2aaf1449",
        "parallel_moe.Q32.fresh1": "4c75570d123b7c09"},
    "PARALLEL_MOE_RAGGED": {
        "parallel_moe.Q1.fresh0": "d72b430cfe086633",
        "parallel_moe.Q1.fresh1": "d72b430cfe086633",
        "parallel_moe.Q32.fresh0": "9c73deba737ec37f",
        "parallel_moe.Q32.fresh1": "9c73deba737ec37f"},
    "PARALLEL_MOE_PACKED_RAGGED": {
        "parallel_moe.Q32.fresh1": "d16b0b872de79b9a"},
    # PR 58's own, no parent's: tests/test_kda_latent.py's small model of
    # four layers (the ``Ling-3.0-flash`` family: KDA, KDA, MLA, KDA in ONE
    # latent block: the gated delta rule's step and, in the Q 32 bucket,
    # its chunked form's ``while`` and ``triangular_solve`` over the
    # manager's six states beside ONE latent pool layer whose query has no
    # low-rank step; a group-limited sigmoid router, ``moe_group_select``,
    # over 4 of 16 experts held).  4 slots x 32 rows stay padded and take
    # ``ragged_paged_mla``; a latent wave has no fresh variant, so a
    # bucket's two programs are one text.  Every entry above is the
    # parent's: the new ``BlockSpec.kda``, ``LatentSpec.q_lora_rank`` 0 and
    # ``RoutedSpec.n_group`` / ``topk_group`` trace nothing at their
    # defaults.  PR 59 (the chunked form as the kernel ``kda_chunk_scan``
    # wherever ``kda_decode.takes_kernel`` says so): these eight STAND as
    # PR 58 wrote them, because the small model's heads are 16 wide (and
    # a q-block of 32 is no whole chunk of 64), so its programs keep
    # ``kda_chunked``, whose trace ``kda_mixer`` leaves as it was.
    "KDA_LATENT_MASKED": {
        "kda_latent.Q1.fresh0": "a4214555e594b6bb",
        "kda_latent.Q1.fresh1": "a4214555e594b6bb",
        "kda_latent.Q32.fresh0": "03d16ee894843498",
        "kda_latent.Q32.fresh1": "03d16ee894843498"},
    "KDA_LATENT_RAGGED": {
        "kda_latent.Q1.fresh0": "610eca4a3607a5ef",
        "kda_latent.Q1.fresh1": "610eca4a3607a5ef",
        "kda_latent.Q32.fresh0": "d7d792f69ba556c3",
        "kda_latent.Q32.fresh1": "d7d792f69ba556c3"},
    # PR 62's own, no parent's: tests/test_kda_gqa.py's small model of the
    # ``solar_open2`` block (GQA, KDA, KDA, KDA; heads of 128, so that the
    # Q 64 programs take ``kda_chunk_scan`` with ``exact``: the kernel's
    # level-by-level pairing is in the text, interpreted in the masked
    # family and as Mosaic's assembly in the other).  PR 62 changed NO
    # accepted program: the 97 entries above stand as PR 61 left them (the
    # new ``BlockSpec.attn_gate`` and ``KDASpec.decay`` / ``rank`` /
    # ``gate_by`` / ``beta_scale`` trace nothing at their defaults, and
    # ``kda_chunked``'s sub-block scoring moved into a helper of the same
    # operations in the same order).
    "KDA_GQA_MASKED": {
        "kda_gqa.Q1.fresh0": "81c477bd9d182f78",
        "kda_gqa.Q1.fresh1": "be3e10a67c8aef92",
        "kda_gqa.Q64.fresh0": "ccbae64e8352ec7b",
        "kda_gqa.Q64.fresh1": "16ac7062bd635530"},
    "KDA_GQA_RAGGED": {
        "kda_gqa.Q1.fresh0": "c412ddf6db5f419c",
        "kda_gqa.Q1.fresh1": "c412ddf6db5f419c",
        "kda_gqa.Q64.fresh0": "00c0715dd0c411a6",
        "kda_gqa.Q64.fresh1": "00c0715dd0c411a6"},
}


@pytest.fixture(scope="module")
def digests(request):
    """``family -> {program: digest}``, each family lowered once."""
    done = {}

    def of(family):
        if family not in done:
            build, kernels = FAMILIES[family]
            if not kernels:
                done[family] = {k: digest(low.as_text()) for k, low in
                                build(jax.ShapeDtypeStruct).items()}
                return done[family]
            from hetu_tpu.kernels import grouped_matmul as gm
            from hetu_tpu.kernels import kda_scan as ks
            from hetu_tpu.kernels import paged_kv_write as pw
            from hetu_tpu.kernels import ragged_attention as ra
            from hetu_tpu.kernels import ssm_step as ss
            on_chip = request.getfixturevalue("sds")
            with pytest.MonkeyPatch.context() as m:
                for module in (ra, gm, ss, pw, ks):
                    m.setattr(module, "_use_interpret", lambda: False)
                texts = {k: low.as_text()
                         for k, low in build(on_chip).items()}
            assert all("tpu_custom_call" in t for t in texts.values())
            done[family] = {k: digest(strip_kernel_locations(t))
                            for k, t in texts.items()}
        return done[family]
    return of


@pytest.mark.parametrize("family,program", [
    (f, p) for f, table in PARENT.items() for p in table])
def test_a_program_lowers_to_the_parents_text(digests, family, program):
    assert digests(family)[program] == PARENT[family][program]
