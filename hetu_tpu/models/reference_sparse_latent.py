"""The ``dots3_note`` / DeepSeek-V3.2 family's layer stack in plain float32
``jax.numpy`` under precision ``highest``: the reference the served
logits of ``sparse_latent.SparseLatentConfig`` are set against.  No
cache, no kernels, no batching, the EXPANDED form of latent attention (a
key and a value a head a position), an exact top-``index_topk``.

``h`` the residual, ``x = RMSNorm(h)``, ``d`` the hidden width; RoPE is
rotate-half over the stated columns.

Full layer (``H`` heads, ranks ``dq`` / ``dc``, head sizes ``dn`` + ``dr``
/ ``dv``, ``rope_theta``):

    c_q  = r_q  RMSNorm(x W_qa)            r_q  = sqrt(d / dq) where the
    c_kv = r_kv RMSNorm((x W_kva)[:dc])    configuration rescales, else 1
    k_r  = RoPE((x W_kva)[dc:])            (one for all heads)
    q_h  = c_q W_qb^h -> [q_n | RoPE(q_r)]
    k_hs = [c_kv,s W_uk^h | k_r,s]         v_hs = c_kv,s W_uv^h
    indexer: qI_j = RoPE_rd(c_q W_Iq^j)    (j < J, D wide, the first
             kI_s = RoPE_rd(LayerNorm(x_s W_Ik))       rd columns rotated)
             w_j  = (x W_Iw)_j / sqrt(J D)
             I_ts = sum_j w_tj relu(qI_tj . kI_s)
             S_t  = the ``index_topk`` positions s <= t of largest I_ts
                    (ties by lower position; all while t < index_topk)
    o_h  = sum_{s in S_t} softmax_s(q_h . k_hs / sqrt(dn + dr)) v_hs
    g    = sigmoid(x W_g)  [H]             (where the layer is gated)
    h   <- h + concat_h(g_h o_h) W_o

Sliding layer (the ``swa_*`` sizes and theta): the same without the
indexer, ``S_t = {t - window + 1, .., t}``.

FFN: the first ``first_k_dense_replace`` layers dense gated SiLU; the
others the sigmoid router over ALL ``n_routed_experts`` (the
``num_experts_per_tok`` largest of ``s + b`` chosen, weights ``s``
normalised over all the chosen, scaled), the HELD experts' part (``held``
= (first, count): the leaves hold experts ``[first, first + count)``) and
the shared expert.  Head: ``RMSNorm(h) W_head`` over the rows held.

``control`` computes something else ON PURPOSE (each has to come out not
correct): "nearest" reads the nearest ``index_topk`` positions in place
of the indexer's; "no_selection" reads everything; "window_minus" /
"window_plus" a window one shorter / longer, "window_half" /
"window_double" one half / twice as long; "no_gate", "no_rescale",
"no_index_rope" leave that out; "float8" rounds the operands of every
weight product to float8 (e4m3); "norm_held" normalises the routing
weights over the held experts alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 64
CONTROLS = ("nearest", "no_selection", "window_minus", "window_plus",
            "window_half", "window_double", "no_gate", "no_rescale",
            "no_index_rope", "float8", "norm_held")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mm(lower):
    """The matrix product in float32 ``highest``; with ``lower`` both
    operands are first rounded to float8 (e4m3)."""
    def f32(a):
        if lower:
            a = a.astype(jnp.float8_e4m3fn)
        return a.astype(jnp.float32)
    return lambda a, b: jnp.dot(f32(a), f32(b),
                                precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _attention(h, w, sizes, control):
    """(the layer's attention part, every position's index tie margin:
    the last chosen index score less the first not chosen, ``inf`` where
    a row chose everything or the layer has no indexer)."""
    (H, dq, dc, dn, dr, dv, theta, eps, window, index, gate,
     rescale) = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S, d = h.shape
    if control == "no_rescale":
        rescale = False
    if window:
        window = {"window_minus": window - 1, "window_plus": window + 1,
                  "window_half": window // 2,
                  "window_double": 2 * window}.get(control, window)
    x = _rms(h, f32(w["ln1"]), eps)
    cq = _rms(mm(x, w["q_a"]), f32(w["q_a_norm"]), eps)
    kva = mm(x, w["kv_a"])
    ckv = _rms(kva[:, :dc], f32(w["kv_a_norm"]), eps)
    if rescale:
        cq, ckv = cq * (d / dq) ** 0.5, ckv * (d / dc) ** 0.5
    q = mm(cq, w["q_b"]).reshape(S, H, dn + dr)
    kv = mm(ckv, w["kv_b"]).reshape(S, H, dn + dv)
    k_r = _rope(kva[:, dc:], theta)
    qk = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    kk = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (S, H, dr))], -1)
    v = kv[..., dn:]
    block = min(ROW_BLOCK, S)
    pos = jnp.arange(S)
    if index is not None:
        J, D, topk, rd = index
        K = min(topk, S)

        def rot(a):
            if control == "no_index_rope":
                return a
            return jnp.concatenate([_rope(a[..., :rd], theta), a[..., rd:]],
                                   -1)

        qi = rot(mm(cq, w["index_q"]).reshape(S, J, D))
        ki = mm(x, w["index_k"])
        mu = ki.mean(-1, keepdims=True)
        ki = (ki - mu) * jax.lax.rsqrt(
            ((ki - mu) ** 2).mean(-1, keepdims=True) + eps)
        ki = rot(ki * f32(w["index_k_norm"]) + f32(w["index_k_bias"]))
        wi = mm(x, w["index_w"]) * (J * D) ** -0.5

    def rows(r0):
        t = r0 + jnp.arange(block)
        seen = pos[None, :] <= t[:, None]                   # [block, S]
        tie = jnp.full((block,), jnp.inf, jnp.float32)
        if window:
            seen &= pos[None, :] > t[:, None] - window
        elif index is not None and control != "no_selection":
            if control == "nearest":
                seen &= pos[None, :] > t[:, None] - K
            else:
                s = jnp.einsum(
                    "qjd,sd->qjs",
                    jax.lax.dynamic_slice_in_dim(qi, r0, block, 0), ki,
                    precision=jax.lax.Precision.HIGHEST)
                score = jnp.sum(
                    jnp.maximum(s, 0.0) * jax.lax.dynamic_slice_in_dim(
                        wi, r0, block, 0)[:, :, None], axis=1)
                score = jnp.where(seen, score, -jnp.inf)
                # exact: top_k keeps the lower position of a tie
                top, idx = jax.lax.top_k(score, min(K + 1, S))
                chosen = jnp.zeros((block, S), bool).at[
                    jnp.arange(block)[:, None], idx[:, :K]].set(True)
                seen &= chosen
                if K < S:
                    tie = jnp.where(t >= K, top[:, K - 1] - top[:, K],
                                    jnp.inf)
        qb = jax.lax.dynamic_slice_in_dim(qk, r0, block, 0)
        sc = jnp.einsum("qhd,shd->hqs", qb, kk,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
        p = jax.nn.softmax(
            jnp.where(seen[None], sc * (dn + dr) ** -0.5, -jnp.inf), -1)
        return jnp.einsum("hqs,shd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST), tie

    o, tie = jax.lax.map(rows, jnp.arange(0, S, block))
    o = o.reshape(S, H, dv)
    if gate and control != "no_gate":
        o = o * jax.nn.sigmoid(mm(x, w["gate"]))[:, :, None]
    return mm(o.reshape(S, H * dv), w["proj"]), tie.reshape(S)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _dense_ffn(h, ln2, wg, wu, wd, eps, lower):
    mm = _mm(lower)
    x = _rms(h, ln2.astype(jnp.float32), eps)
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _experts(h, w, sizes, control):
    """(the routed layer's part, each row's selection margin): the held
    experts one at a time over every row under a dense mask of weights,
    plus the shared expert."""
    k, scale, norm, first, count, shared, eps = sizes
    mm = _mm(control == "float8")
    x = _rms(h, w["ln2"].astype(jnp.float32), eps)
    s = jax.nn.sigmoid(mm(x, w["router"]))                  # [S, E]
    pick = s + w["bias"].astype(jnp.float32)
    ranked = jnp.sort(pick, axis=-1)[:, ::-1]
    chosen = pick >= ranked[:, k - 1:k]
    ids = first + jnp.arange(count)
    over = chosen
    if control == "norm_held":
        over = chosen & jnp.zeros((s.shape[1],), bool).at[ids].set(True)
    wts = jnp.where(chosen, s, 0.0)
    if norm:
        wts = wts / (jnp.where(over, s, 0.0).sum(-1, keepdims=True) + 1e-20)
    wts = wts * scale

    def one(r, e):
        wg, wu, wd, we = e
        return r + we[:, None] * mm(jax.nn.silu(mm(x, wg)) * mm(x, wu),
                                    wd), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["gate"], w["up"], w["down"], wts[:, ids].T))
    if shared:
        r = r + mm(jax.nn.silu(mm(x, w["shared_gate"]))
                   * mm(x, w["shared_up"]), w["shared_down"])
    return r, ranked[:, k - 1] - ranked[:, k]


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(h_rows, ln_f, head, eps, lower):
    return _mm(lower)(_rms(h_rows, ln_f.astype(jnp.float32), eps), head)


def layer_sizes(config, i):
    """Layer ``i``'s static sizes for ``_attention`` from the source's
    keys."""
    c = config
    full = c["layer_types"][i] == "full_attention"
    p = "" if full else "swa_"
    index = None
    if full and c.get("index_topk"):
        index = (c["index_n_heads"], c["index_head_dim"], c["index_topk"],
                 c["qk_rope_head_dim"])
    return (c[f"{p}num_attention_heads"], c[f"{p}q_lora_rank"],
            c[f"{p}kv_lora_rank"],
            c[f"{p}qk_nope_head_dim"], c[f"{p}qk_rope_head_dim"],
            c[f"{p}v_head_dim"], float(c[f"{p}rope_theta"]),
            float(c["rms_norm_eps"]),
            0 if full else int(c["sliding_window_size"]), index,
            c.get(f"{p}attention_gate_type") == "headwise",
            bool(c.get("apply_mla_qkv_lora_rescale")))


def forward(params, config, tokens, rows, name="d3n", held=None,
            control=None, stats=None):
    """(logits [len(rows), V held] as numpy float32, margin [S], tie [S])
    for the sequence ``tokens`` [S] (padded here to whole row blocks:
    causal, so what lies behind the real tokens moves nothing before
    it): the next-token logits after each position in ``rows``,
    every position's smallest routing selection margin over the routed
    layers and its smallest index tie margin over the layers with an
    indexer.  ``config`` holds the source's keys, ``n_routed_experts``
    the ROUTER's width; ``held`` (first, count) says which experts the
    leaves hold (all, by default).  ``stats`` (a dict) receives, a
    layer, the RMS of the residual and of its two parts, and the
    logits' standard deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    eps = float(c["rms_norm_eps"])
    lower = control == "float8"
    first, count = held or (0, c["n_routed_experts"])
    moe_sizes = (c["num_experts_per_tok"],
                 float(c.get("routed_scaling_factor", 1.0)),
                 bool(c.get("norm_topk_prob", True)), int(first),
                 int(count), bool(c.get("n_shared_experts", 0)), eps)
    n = len(tokens)
    if n > ROW_BLOCK and n % ROW_BLOCK:
        # whole row blocks: what is padded lies behind every real row
        tokens = np.concatenate([np.asarray(tokens, np.int32), np.zeros(
            -n % ROW_BLOCK, np.int32)])
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    rms_of = lambda a: float(np.sqrt(np.mean(np.square(    # noqa: E731
        np.asarray(a)[:n]))))
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    margin = np.full(S, np.inf, np.float32)
    tie = np.full(S, np.inf, np.float32)
    for i in range(c["num_hidden_layers"]):
        us = f"{name}_h{i}"
        sizes = layer_sizes(c, i)
        w = {"ln1": params[f"{us}_ln1_scale"],
             "q_a": params[f"{us}_attn_q_a_weight"],
             "q_a_norm": params[f"{us}_attn_q_a_norm_scale"],
             "q_b": params[f"{us}_attn_q_b_weight"],
             "kv_a": params[f"{us}_attn_kv_a_weight"],
             "kv_a_norm": params[f"{us}_attn_kv_a_norm_scale"],
             "kv_b": params[f"{us}_attn_kv_b_weight"],
             "proj": params[f"{us}_attn_proj_weight"]}
        if sizes[10]:
            w["gate"] = params[f"{us}_attn_gate_weight"]
        if sizes[9] is not None:
            w.update(index_q=params[f"{us}_attn_index_q_weight"],
                     index_k=params[f"{us}_attn_index_k_weight"],
                     index_k_norm=params[f"{us}_attn_index_k_norm_scale"],
                     index_k_bias=params[f"{us}_attn_index_k_norm_bias"],
                     index_w=params[f"{us}_attn_index_w_weight"])
        part, gap = _attention(
            h, w, sizes, None if control == "norm_held" else control)
        tie = np.minimum(tie, np.asarray(gap))
        layer = {"kind": c["layer_types"][i], "residual": rms_of(h),
                 "attention": rms_of(part)} if stats is not None else None
        h = h + part
        if i < c.get("first_k_dense_replace", 0):
            part = _dense_ffn(h, params[f"{us}_ln2_scale"],
                              params[f"{us}_ffn_gate_weight"],
                              params[f"{us}_ffn_up_weight"],
                              params[f"{us}_ffn_down_weight"], eps, lower)
        else:
            we = {"ln2": params[f"{us}_ln2_scale"],
                  "router": params[f"{us}_moe_router_weight"],
                  "bias": params[f"{us}_moe_router_bias"],
                  "gate": params[f"{us}_moe_experts_gate"],
                  "up": params[f"{us}_moe_experts_up"],
                  "down": params[f"{us}_moe_experts_down"]}
            if moe_sizes[5]:
                we.update(
                    shared_gate=params[f"{us}_moe_shared_gate_weight"],
                    shared_up=params[f"{us}_moe_shared_up_weight"],
                    shared_down=params[f"{us}_moe_shared_down_weight"])
            part, gap = _experts(
                h, we, moe_sizes,
                control if control in ("float8", "norm_held") else None)
            margin = np.minimum(margin, np.asarray(gap))
        if stats is not None:
            layer["ffn"] = rms_of(part)
            stats.setdefault("layers", []).append(layer)
        h = h + part
    logits = np.asarray(_head(
        h[jnp.asarray(rows, jnp.int32)], params[f"{name}_ln_f_scale"],
        params[f"{name}_lm_head_weight"], eps, lower))
    if stats is not None:
        stats["logits"] = float(logits.std())
    return logits, margin[:n], tie[:n]
