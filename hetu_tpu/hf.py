"""HuggingFace checkpoint import: torch ``state_dict`` -> hetu_tpu
parameter dicts for the BERT and GPT-2 families.

Beyond-reference interop (the reference has no pretrained-weight
import): a ``transformers`` user loads their checkpoint into this
framework with one call and the forward pass matches the canonical
implementation numerically — the parity tests in tests/test_hf.py run
the SAME random weights through transformers (torch) and through this
framework's executor and compare outputs.

Layout notes:
* torch ``nn.Linear`` stores [out, in] — transposed into our [in, out];
* HF GPT-2 uses ``Conv1D`` with [in, out] — NOT transposed; its fused
  ``c_attn`` [in, 3H] is split into our q/k/v;
* our gelu is the tanh approximation (reference kernel parity), which
  equals HF's ``gelu_new`` — BERT checkpoints trained with exact gelu
  import fine but carry the usual ~1e-3 activation difference; the
  parity tests pin ``hidden_act='gelu_new'``.

Use:
    params = ht.hf.convert_bert(torch_model.state_dict())
    executor.load_dict(params)
"""

from __future__ import annotations

import numpy as np

__all__ = ["convert_bert", "convert_bert_pretraining_heads",
           "convert_bert_classifier", "convert_bert_qa",
           "convert_gpt2", "convert_glm4_moe_lite", "export_bert",
           "export_bert_classifier",
           "export_bert_qa", "export_gpt2"]


def _np(t):
    if hasattr(t, "detach"):
        # .float() first: torch's .numpy() rejects bfloat16 tensors
        # (bf16-loaded checkpoints must still import in one call)
        t = t.detach().float().cpu().numpy()
    return np.asarray(t, np.float32)


def _lin(sd, key):
    """torch Linear -> (weight [in,out], bias)."""
    return _np(sd[key + ".weight"]).T.copy(), _np(sd[key + ".bias"])


def convert_bert(state_dict, name="bert", prefix=""):
    """HF ``BertModel`` weights -> {our param name: array}.

    ``prefix``: the HF-side key prefix when the backbone is nested
    (e.g. ``"bert."`` inside BertForPreTraining)."""
    sd = {k[len(prefix):]: v for k, v in state_dict.items()
          if k.startswith(prefix)}
    out = {}
    emb = f"{name}_embeddings"
    out[f"{emb}_word_embeddings"] = _np(
        sd["embeddings.word_embeddings.weight"])
    out[f"{emb}_position_embeddings"] = _np(
        sd["embeddings.position_embeddings.weight"])
    out[f"{emb}_token_type_embeddings"] = _np(
        sd["embeddings.token_type_embeddings.weight"])
    out[f"{emb}_ln_scale"] = _np(sd["embeddings.LayerNorm.weight"])
    out[f"{emb}_ln_bias"] = _np(sd["embeddings.LayerNorm.bias"])

    i = 0
    while f"encoder.layer.{i}.attention.self.query.weight" in sd:
        hf = f"encoder.layer.{i}"
        us = f"{name}_layer{i}"
        for hname, uname in (("attention.self.query", "attn_q"),
                             ("attention.self.key", "attn_k"),
                             ("attention.self.value", "attn_v"),
                             ("attention.output.dense", "attn_proj"),
                             ("intermediate.dense", "intermediate"),
                             ("output.dense", "output")):
            w, b = _lin(sd, f"{hf}.{hname}")
            out[f"{us}_{uname}_weight"] = w
            out[f"{us}_{uname}_bias"] = b
        out[f"{us}_attn_ln_scale"] = _np(
            sd[f"{hf}.attention.output.LayerNorm.weight"])
        out[f"{us}_attn_ln_bias"] = _np(
            sd[f"{hf}.attention.output.LayerNorm.bias"])
        out[f"{us}_out_ln_scale"] = _np(
            sd[f"{hf}.output.LayerNorm.weight"])
        out[f"{us}_out_ln_bias"] = _np(sd[f"{hf}.output.LayerNorm.bias"])
        i += 1

    if "pooler.dense.weight" in sd:
        w, b = _lin(sd, "pooler.dense")
        out[f"{name}_pooler_dense_weight"] = w
        out[f"{name}_pooler_dense_bias"] = b
    return out


def convert_bert_pretraining_heads(state_dict, name="bert"):
    """HF ``BertForPreTraining`` -> backbone + MLM/NSP head params."""
    out = convert_bert(state_dict, name=name, prefix="bert.")
    sd = state_dict
    w, b = _lin(sd, "cls.predictions.transform.dense")
    out[f"{name}_mlm_transform_weight"] = w
    out[f"{name}_mlm_transform_bias"] = b
    out[f"{name}_mlm_ln_scale"] = _np(
        sd["cls.predictions.transform.LayerNorm.weight"])
    out[f"{name}_mlm_ln_bias"] = _np(
        sd["cls.predictions.transform.LayerNorm.bias"])
    out[f"{name}_mlm_bias"] = _np(sd["cls.predictions.bias"])
    w, b = _lin(sd, "cls.seq_relationship")
    out[f"{name}_nsp_weight"] = w
    out[f"{name}_nsp_bias"] = b
    return out


def convert_bert_classifier(state_dict, name="bert"):
    """HF ``BertForSequenceClassification`` -> backbone + classifier
    params (the import path for fine-tuning an HF-pretrained BERT
    through the GLUE pipeline)."""
    out = convert_bert(state_dict, name=name, prefix="bert.")
    w, b = _lin(state_dict, "classifier")
    out[f"{name}_classifier_weight"] = w
    out[f"{name}_classifier_bias"] = b
    return out


def convert_bert_qa(state_dict, name="bert"):
    """HF ``BertForQuestionAnswering`` -> backbone + qa_outputs params
    (the import path for fine-tuning an HF-pretrained BERT through the
    SQuAD pipeline — hetu_tpu.squad + BertForQuestionAnswering)."""
    out = convert_bert(state_dict, name=name, prefix="bert.")
    w, b = _lin(state_dict, "qa_outputs")
    out[f"{name}_qa_outputs_weight"] = w
    out[f"{name}_qa_outputs_bias"] = b
    return out


def convert_gpt2(state_dict, name="gpt", prefix=""):
    """HF ``GPT2Model`` weights -> {our param name: array}.

    GPT-2's Conv1D weights are already [in, out]; the fused c_attn
    [H, 3H] splits into our separate q/k/v projections."""
    sd = {k[len(prefix):]: v for k, v in state_dict.items()
          if k.startswith(prefix)}
    out = {
        f"{name}_wte_table": _np(sd["wte.weight"]),
        f"{name}_wpe": _np(sd["wpe.weight"]),
        f"{name}_ln_f_scale": _np(sd["ln_f.weight"]),
        f"{name}_ln_f_bias": _np(sd["ln_f.bias"]),
    }
    i = 0
    while f"h.{i}.ln_1.weight" in sd:
        hf = f"h.{i}"
        us = f"{name}_h{i}"
        out[f"{us}_ln1_scale"] = _np(sd[f"{hf}.ln_1.weight"])
        out[f"{us}_ln1_bias"] = _np(sd[f"{hf}.ln_1.bias"])
        out[f"{us}_ln2_scale"] = _np(sd[f"{hf}.ln_2.weight"])
        out[f"{us}_ln2_bias"] = _np(sd[f"{hf}.ln_2.bias"])
        ca_w = _np(sd[f"{hf}.attn.c_attn.weight"])     # [H, 3H]
        ca_b = _np(sd[f"{hf}.attn.c_attn.bias"])       # [3H]
        H = ca_w.shape[0]
        for j, nm in enumerate(("q", "k", "v")):
            out[f"{us}_attn_{nm}_weight"] = \
                ca_w[:, j * H:(j + 1) * H].copy()
            out[f"{us}_attn_{nm}_bias"] = ca_b[j * H:(j + 1) * H].copy()
        out[f"{us}_attn_proj_weight"] = _np(sd[f"{hf}.attn.c_proj.weight"])
        out[f"{us}_attn_proj_bias"] = _np(sd[f"{hf}.attn.c_proj.bias"])
        out[f"{us}_ffn_wi_weight"] = _np(sd[f"{hf}.mlp.c_fc.weight"])
        out[f"{us}_ffn_wi_bias"] = _np(sd[f"{hf}.mlp.c_fc.bias"])
        out[f"{us}_ffn_wo_weight"] = _np(sd[f"{hf}.mlp.c_proj.weight"])
        out[f"{us}_ffn_wo_bias"] = _np(sd[f"{hf}.mlp.c_proj.bias"])
        i += 1
    return out


def convert_glm4_moe_lite(state_dict, config, name="glm", prefix="model."):
    """HF ``glm4_moe_lite`` (the DeepSeek-V3 layout: MLA attention, a
    sigmoid ``noaux_tc`` router with ``e_score_correction_bias``,
    per-expert ``gate/up/down_proj``, ``shared_experts``) weights ->
    the serving parameter dict of ``models.moe_decode.LatentMoEConfig``
    (``config``; ``param_shapes`` lists the leaves).

    torch ``Linear`` weights are [out, in] and are transposed; the
    experts of a layer are stacked into [E, in, out] leaves;
    ``kv_b_proj`` stays WHOLE (the serving step splits it into its key
    and value halves when it absorbs them).  The checkpoints' RoPE pairs
    neighbouring columns (2j, 2j+1); this repo rotates halves (j,
    j + d/2), so the rope columns of ``q_b_proj`` (every head's) and of
    ``kv_a_proj_with_mqa`` are permuted once, here: evens first, then
    odds — the same permutation on both sides of the score, which it
    leaves unchanged.  Layers past ``config.num_hidden_layers`` (the
    multi-token-prediction layer) are dropped, as the checkpoints' own
    loaders drop them at inference."""
    c = config
    sd = state_dict
    H, dn, dr = (c.num_attention_heads, c.qk_nope_head_dim,
                 c.qk_rope_head_dim)
    dc = c.kv_lora_rank
    halves = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])

    def lin(key):
        return _np(sd[key]).T.copy()

    out = {f"{name}_wte_table": _np(sd[f"{prefix}embed_tokens.weight"]),
           f"{name}_ln_f_scale": _np(sd[f"{prefix}norm.weight"])}
    if not c.tie_word_embeddings:
        out[f"{name}_lm_head_weight"] = lin("lm_head.weight")
    for i in range(c.num_hidden_layers):
        hf, us = f"{prefix}layers.{i}", f"{name}_h{i}"
        at = f"{hf}.self_attn"
        q_b = lin(f"{at}.q_b_proj.weight").reshape(-1, H, dn + dr)
        q_b = np.concatenate([q_b[..., :dn], q_b[..., dn:][..., halves]], -1)
        kv_a = lin(f"{at}.kv_a_proj_with_mqa.weight")
        kv_a = np.concatenate([kv_a[:, :dc], kv_a[:, dc:][:, halves]], -1)
        out.update({
            f"{us}_ln1_scale": _np(sd[f"{hf}.input_layernorm.weight"]),
            f"{us}_ln2_scale": _np(
                sd[f"{hf}.post_attention_layernorm.weight"]),
            f"{us}_attn_q_a_weight": lin(f"{at}.q_a_proj.weight"),
            f"{us}_attn_q_a_norm_scale": _np(
                sd[f"{at}.q_a_layernorm.weight"]),
            f"{us}_attn_q_b_weight": q_b.reshape(q_b.shape[0], -1),
            f"{us}_attn_kv_a_weight": kv_a,
            f"{us}_attn_kv_a_norm_scale": _np(
                sd[f"{at}.kv_a_layernorm.weight"]),
            f"{us}_attn_kv_b_weight": lin(f"{at}.kv_b_proj.weight"),
            f"{us}_attn_proj_weight": lin(f"{at}.o_proj.weight")})
        mlp = f"{hf}.mlp"
        if i < c.first_k_dense_replace:
            for nm in ("gate", "up", "down"):
                out[f"{us}_ffn_{nm}_weight"] = lin(f"{mlp}.{nm}_proj.weight")
            continue
        out[f"{us}_moe_router_weight"] = lin(f"{mlp}.gate.weight").astype(
            np.float32)
        out[f"{us}_moe_router_bias"] = _np(
            sd[f"{mlp}.gate.e_score_correction_bias"]).astype(np.float32)
        for nm in ("gate", "up", "down"):
            out[f"{us}_moe_experts_{nm}"] = np.stack(
                [lin(f"{mlp}.experts.{e}.{nm}_proj.weight")
                 for e in range(c.n_routed_experts)])
            if c.n_shared_experts:
                out[f"{us}_moe_shared_{nm}_weight"] = lin(
                    f"{mlp}.shared_experts.{nm}_proj.weight")
    return out


def convert_lfm2_moe(state_dict, config, name="lfm", prefix="model."):
    """HF ``lfm2_moe`` weights (gated short convolutions ``conv.in_proj``
    / ``conv.conv`` / ``conv.out_proj``, grouped-query ``self_attn`` with
    ``q_layernorm`` / ``k_layernorm``, a dense ``feed_forward.w1/w3/w2``
    in the leading layers and then ``feed_forward.gate``, ``expert_bias``
    and per-expert ``w1/w3/w2``) -> the serving parameter dict of
    ``models.moe_decode.HybridMoEConfig`` (``config``; ``param_shapes``
    lists the leaves).

    torch ``Linear`` weights are [out, in] and are transposed; the
    depthwise ``Conv1d`` weight ``[D, 1, K]`` becomes ``[K, D]`` (tap
    ``j`` weighs the input ``K - 1 - j`` positions back, as torch's
    left-padded cross-correlation has it); the experts of a layer are
    stacked into [E, in, out] leaves (w1 the gate, w3 the up, w2 the
    down projection).  The family rotates halves, as this repo does, so
    no column is permuted.  The head is the embedding table.  A
    checkpoint without ``expert_bias`` (``use_expert_bias`` false) gets
    a zero one."""
    c = config
    sd = state_dict

    def lin(key):
        return _np(sd[key]).T.copy()

    out = {f"{name}_wte_table": _np(sd[f"{prefix}embed_tokens.weight"]),
           f"{name}_ln_f_scale": _np(sd[f"{prefix}embedding_norm.weight"])}
    ffn_names = (("gate", "w1"), ("up", "w3"), ("down", "w2"))
    for i, op in enumerate(c.operators()):
        hf, us = f"{prefix}layers.{i}", f"{name}_h{i}"
        out[f"{us}_ln1_scale"] = _np(sd[f"{hf}.operator_norm.weight"])
        out[f"{us}_ln2_scale"] = _np(sd[f"{hf}.ffn_norm.weight"])
        if op == "conv":
            out[f"{us}_conv_in_weight"] = lin(f"{hf}.conv.in_proj.weight")
            out[f"{us}_conv_weight"] = _np(
                sd[f"{hf}.conv.conv.weight"])[:, 0, :].T.copy()
            out[f"{us}_conv_out_weight"] = lin(f"{hf}.conv.out_proj.weight")
        else:
            at = f"{hf}.self_attn"
            for nm in ("q", "k", "v"):
                out[f"{us}_attn_{nm}_weight"] = lin(f"{at}.{nm}_proj.weight")
            out[f"{us}_attn_q_norm_scale"] = _np(
                sd[f"{at}.q_layernorm.weight"])
            out[f"{us}_attn_k_norm_scale"] = _np(
                sd[f"{at}.k_layernorm.weight"])
            out[f"{us}_attn_proj_weight"] = lin(f"{at}.out_proj.weight")
        ff = f"{hf}.feed_forward"
        if i < c.num_dense_layers:
            for ours, theirs in ffn_names:
                out[f"{us}_ffn_{ours}_weight"] = lin(f"{ff}.{theirs}.weight")
            continue
        out[f"{us}_moe_router_weight"] = lin(f"{ff}.gate.weight").astype(
            np.float32)
        bias = sd.get(f"{ff}.expert_bias")
        out[f"{us}_moe_router_bias"] = (
            np.zeros(c.n_routed_experts, np.float32) if bias is None
            else _np(bias).astype(np.float32))
        for ours, theirs in ffn_names:
            out[f"{us}_moe_experts_{ours}"] = np.stack(
                [lin(f"{ff}.experts.{e}.{theirs}.weight")
                 for e in range(c.n_routed_experts)])
    return out


# ------------------------------------------------------------------ #
# the REVERSE direction: our trained parameters -> HF state_dicts, so
# models trained here load into transformers (torch) for serving /
# evaluation in that ecosystem.  Exact inverses of the importers.
# ------------------------------------------------------------------ #

def _t(arr):
    import torch
    return torch.from_numpy(np.ascontiguousarray(np.asarray(arr),
                                                 np.float32))


def export_bert(params, name="bert", prefix=""):
    """{our param name: array} -> HF ``BertModel`` state_dict keys
    (load with ``hf_model.load_state_dict(out, strict=False)``)."""
    p = {k[len(name) + 1:]: v for k, v in params.items()
         if k.startswith(name + "_")}
    out = {}

    def put(hf_key, arr, transpose=False):
        a = np.asarray(arr)
        out[prefix + hf_key] = _t(a.T if transpose else a)

    put("embeddings.word_embeddings.weight",
        p["embeddings_word_embeddings"])
    put("embeddings.position_embeddings.weight",
        p["embeddings_position_embeddings"])
    if "embeddings_token_type_embeddings" in p:
        put("embeddings.token_type_embeddings.weight",
            p["embeddings_token_type_embeddings"])
    put("embeddings.LayerNorm.weight", p["embeddings_ln_scale"])
    put("embeddings.LayerNorm.bias", p["embeddings_ln_bias"])
    i = 0
    while f"layer{i}_attn_q_weight" in p:
        us = f"layer{i}"
        hf = f"encoder.layer.{i}"
        for uname, hname in (("attn_q", "attention.self.query"),
                             ("attn_k", "attention.self.key"),
                             ("attn_v", "attention.self.value"),
                             ("attn_proj", "attention.output.dense"),
                             ("intermediate", "intermediate.dense"),
                             ("output", "output.dense")):
            put(f"{hf}.{hname}.weight", p[f"{us}_{uname}_weight"],
                transpose=True)
            put(f"{hf}.{hname}.bias", p[f"{us}_{uname}_bias"])
        put(f"{hf}.attention.output.LayerNorm.weight",
            p[f"{us}_attn_ln_scale"])
        put(f"{hf}.attention.output.LayerNorm.bias",
            p[f"{us}_attn_ln_bias"])
        put(f"{hf}.output.LayerNorm.weight", p[f"{us}_out_ln_scale"])
        put(f"{hf}.output.LayerNorm.bias", p[f"{us}_out_ln_bias"])
        i += 1
    if "pooler_dense_weight" in p:
        put("pooler.dense.weight", p["pooler_dense_weight"],
            transpose=True)
        put("pooler.dense.bias", p["pooler_dense_bias"])
    return out


def _export_bert_with_head(params, name, head_param, hf_head):
    """Backbone under ``bert.`` + one Linear head — the shared shape of
    the classifier/QA exporters (exact inverses of their importers)."""
    out = export_bert(params, name=name, prefix="bert.")
    w = np.asarray(params[f"{name}_{head_param}_weight"])
    b = np.asarray(params[f"{name}_{head_param}_bias"])
    out[f"{hf_head}.weight"] = _t(w.T)
    out[f"{hf_head}.bias"] = _t(b)
    return out


def export_bert_classifier(params, name="bert"):
    """Our fine-tuned classifier -> HF ``BertForSequenceClassification``
    state_dict (serve a GLUE model from transformers)."""
    return _export_bert_with_head(params, name, "classifier",
                                  "classifier")


def export_bert_qa(params, name="bert"):
    """Our fine-tuned span head -> HF ``BertForQuestionAnswering``
    state_dict (serve a SQuAD model from transformers)."""
    return _export_bert_with_head(params, name, "qa_outputs",
                                  "qa_outputs")


def export_gpt2(params, name="gpt", prefix=""):
    """{our param name: array} -> HF ``GPT2Model`` state_dict keys
    (Conv1D layout kept; q/k/v re-fused into c_attn)."""
    p = {k[len(name) + 1:]: v for k, v in params.items()
         if k.startswith(name + "_")}
    out = {
        prefix + "wte.weight": _t(p["wte_table"]),
        prefix + "wpe.weight": _t(p["wpe"]),
        prefix + "ln_f.weight": _t(p["ln_f_scale"]),
        prefix + "ln_f.bias": _t(p["ln_f_bias"]),
    }
    i = 0
    while f"h{i}_ln1_scale" in p:
        us = f"h{i}"
        hf = prefix + f"h.{i}"
        out[f"{hf}.ln_1.weight"] = _t(p[f"{us}_ln1_scale"])
        out[f"{hf}.ln_1.bias"] = _t(p[f"{us}_ln1_bias"])
        out[f"{hf}.ln_2.weight"] = _t(p[f"{us}_ln2_scale"])
        out[f"{hf}.ln_2.bias"] = _t(p[f"{us}_ln2_bias"])
        out[f"{hf}.attn.c_attn.weight"] = _t(np.concatenate(
            [np.asarray(p[f"{us}_attn_{nm}_weight"])
             for nm in ("q", "k", "v")], axis=1))
        out[f"{hf}.attn.c_attn.bias"] = _t(np.concatenate(
            [np.asarray(p[f"{us}_attn_{nm}_bias"])
             for nm in ("q", "k", "v")]))
        out[f"{hf}.attn.c_proj.weight"] = _t(p[f"{us}_attn_proj_weight"])
        out[f"{hf}.attn.c_proj.bias"] = _t(p[f"{us}_attn_proj_bias"])
        out[f"{hf}.mlp.c_fc.weight"] = _t(p[f"{us}_ffn_wi_weight"])
        out[f"{hf}.mlp.c_fc.bias"] = _t(p[f"{us}_ffn_wi_bias"])
        out[f"{hf}.mlp.c_proj.weight"] = _t(p[f"{us}_ffn_wo_weight"])
        out[f"{hf}.mlp.c_proj.bias"] = _t(p[f"{us}_ffn_wo_bias"])
        i += 1
    return out
