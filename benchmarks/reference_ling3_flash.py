"""Ling-3.0-flash's layer stack in plain float32 ``jax.numpy``, precision
``highest``: the benchmark's own copy of the reference (the equations of
``hetu_tpu/models/reference_kda_latent.py``, written again here and not
imported: the yardstick must not move with the program), laid out so that
a 17,000-position request fits on the chip beside 7.3 GB of served
weights.  It decides ``correct``.  No cache, no kernels, no batching, no
chunks: the KDA recurrence STEP BY STEP (a scan over positions), the
latent attention in the EXPANDED form (a key and a value a head a
position).

``h`` the residual, ``u = RMSNorm(h)``, ``d`` the hidden width, ``H``
heads of ``D``.

KDA layer (every layer but the last of each ``layer_group_size``):

    [q~ | k~ | v~] = u W_qkv; every column through the causal conv of K
    taps (zeros before the sequence), then SiLU
    q = l2(q~) D^-1/2, k = l2(k~) a head (l2 x = x rsqrt(sum x^2 + 1e-6))
    g_t = lower_bound sigmoid(exp(A_log_h) (u W_f + dt_bias))   a channel
    beta_t = sigmoid(u W_beta)                                  a head
    S' = Diag(exp g_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T q_t
    y = RMSNorm_D(o_t) scale_h sigmoid(u W_g)_h;  h <- h + concat(y) W_o

MLA layer: q = u W_q -> [q_n | RoPE(q_r)] a head (ONE projection, no
norm); [c | k_r] = u W_kva, c <- RMSNorm(c), k_r <- RoPE(k_r); [k_n | v]
= c W_kvb a head; causal softmax((q_n . k_n + q_r . k_r) / sqrt(dn +
dr)) v; times sigmoid(u W_gate)_h; W_o.  RoPE is rotate-half over the
``qk_rope_head_dim`` columns, ``rope_theta``.

FFN: the first ``first_k_dense_replace`` layers dense gated SiLU; the
others s = sigmoid(x W_r) over ALL ``num_experts``, choice scores s + b
in ``n_group`` groups, a group's score the sum of its two largest, the
``topk_group`` best groups kept, the ``num_experts_per_tok`` largest s + b
among their experts chosen, weights s at the chosen normalised over all
the chosen, scaled; the HELD experts' part (``held`` = (first, count)) and
the shared expert.  Head: ``RMSNorm(h) W_head`` over the rows held.

``control`` computes something else ON PURPOSE (each has to come out not
correct): "no_decay" (alpha 1), "no_delta" (``S' + beta k v^T``: gated
linear attention), "softplus_gate" (``g = -exp(A_log) softplus(u W_f +
dt_bias)``, no lower bound), "conv_cut" (the conv's history dropped at
every multiple of ``CONV_CUT`` positions: tails not carried from one
prompt chunk to the next), "plain_topk" (the choice among all experts),
"group_max" (a group's score its largest one), "no_gate" (the MLA gate
off), "mla_at_4" (the last two layers in the other order: the MLA layer
at place 4), "no_rope", "float8" (the operands of every weight product
rounded to float8 e4m3).

What differs from the program's copy is only how the work is cut: the
sequence is padded to a multiple of ``pad_to`` (causal; a padded position
moves no state: its ``g``, ``beta`` and ``k`` are 0) and the wanted rows
to a multiple of ``ROWS_PAD``; the attention takes the heads
``HEAD_BLOCK`` at a time and inside a head block the query rows
``ROW_BLOCK`` at a time; every part of every layer is one jitted call
whose weights are upcast inside it, the held experts one at a time by a
``lax.scan``.  ``probes`` [M, H, D] (unit-scale queries) are answered by
every KDA layer's state after the sequence's last real position: ``S^T
r``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 64
HEAD_BLOCK = 16
ROWS_PAD = 64
CONV_CUT = 256
CONTROLS = ("no_decay", "no_delta", "softplus_gate", "conv_cut",
            "plain_topk", "group_max", "no_gate", "mla_at_4", "no_rope",
            "float8")
HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


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
    return lambda a, b: jnp.dot(f32(a), f32(b), precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _kda(h, w, n, probes, sizes, control):
    """(the KDA layer's part [S, d]; what ``probes`` [M, H, D] read in the
    state after position ``n - 1``: [M, H, D])."""
    H, D, K, bound, eps = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S = h.shape[0]
    real = jnp.arange(S) < n
    u = _rms(h, f32(w["ln1"]), eps)
    x = mm(u, w["qkv"])                                     # [S, 3 H D]
    taps = f32(w["conv"])
    pos = jnp.arange(S)
    y = 0.0
    for j in range(K):
        back = K - 1 - j
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:S]
        if control == "conv_cut":
            shifted = jnp.where((pos % CONV_CUT >= back)[:, None], shifted,
                                0.0)
        y = y + taps[j] * shifted
    x = jax.nn.silu(y)
    q, k, v = (x[:, j * H * D:(j + 1) * H * D].reshape(S, H, D)
               for j in range(3))
    q, k = _l2(q) * D ** -0.5, _l2(k)
    a = jnp.repeat(jnp.exp(f32(w["A_log"])), D)
    f = mm(u, w["f"]) + f32(w["dt_bias"])
    g = -a * jax.nn.softplus(f) if control == "softplus_gate" \
        else bound * jax.nn.sigmoid(a * f)
    if control == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(mm(u, w["beta"]))                 # [S, H]
    # a padded position moves nothing
    g = jnp.where(real[:, None], g, 0.0).reshape(S, H, D)
    beta = jnp.where(real[:, None], beta, 0.0)
    k = jnp.where(real[:, None, None], k, 0.0)

    def step(St, x):
        qt, kt, vt, gt, bt = x
        St = St * jnp.exp(gt)[..., None]
        r = vt if control == "no_delta" else vt - jnp.einsum(
            "hk,hkv->hv", kt, St, precision=HIGHEST)
        St = St + (bt[:, None] * kt)[..., None] * r[:, None, :]
        return St, jnp.einsum("hk,hkv->hv", qt, St, precision=HIGHEST)

    St, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32),
                         (q, k, v, g, beta))
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * f32(w["norm"]).reshape(H, D)
    o = o * jax.nn.sigmoid(mm(u, w["gate"]))[..., None]
    read = jnp.einsum("mhk,hkv->mhv", probes, St, precision=HIGHEST)
    return mm(o.reshape(S, H * D), w["out"]), read


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _mla(h, w, sizes, control):
    """The latent attention's part [S, d], ``HEAD_BLOCK`` heads at a
    time, ``ROW_BLOCK`` query rows at a time."""
    H, dc, dn, dr, dv, theta, eps = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    rot = (lambda a: a) if control == "no_rope" \
        else (lambda a: _rope(a, theta))
    S = h.shape[0]
    u = _rms(h, f32(w["ln1"]), eps)
    kva = mm(u, w["kv_a"])
    c = _rms(kva[:, :dc], f32(w["kv_a_norm"]), eps)
    k_r = rot(kva[:, dc:])
    hb = min(HEAD_BLOCK, H)
    block = min(ROW_BLOCK, S)
    d = u.shape[1]
    w_q = w["q"].reshape(d, H // hb, hb, dn + dr).transpose(1, 0, 2, 3)
    kv_b = w["kv_b"].reshape(dc, H // hb, hb, dn + dv).transpose(1, 0, 2, 3)
    pos = jnp.arange(S)

    def heads(part):
        wq, wkv = part
        q = mm(u, wq.reshape(d, -1)).reshape(S, hb, dn + dr)
        kv = mm(c, wkv.reshape(dc, -1)).reshape(S, hb, dn + dv)
        qk = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
        kk = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (S, hb, dr))], -1)
        v = kv[..., dn:]

        def rows(r0):
            sc = jnp.einsum(
                "qhd,shd->hqs", jax.lax.dynamic_slice_in_dim(qk, r0, block),
                kk, precision=HIGHEST, preferred_element_type=jnp.float32)
            live = pos[None, :] <= (r0 + jnp.arange(block))[:, None]
            p = jax.nn.softmax(
                jnp.where(live[None], sc * (dn + dr) ** -0.5, -jnp.inf), -1)
            return jnp.einsum("hqs,shd->qhd", p, v, precision=HIGHEST)

        return jax.lax.map(rows, jnp.arange(0, S, block)).reshape(S, hb, dv)

    o = jax.lax.map(heads, (w_q, kv_b))                    # [H/hb, S, hb, dv]
    o = o.transpose(1, 0, 2, 3).reshape(S, H, dv)
    if control != "no_gate":
        o = o * jax.nn.sigmoid(mm(u, w["gate"]))[:, :, None]
    return mm(o.reshape(S, H * dv), w["proj"])


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _dense_ffn(h, ln2, wg, wu, wd, eps, lower):
    mm = _mm(lower)
    x = _rms(h, ln2.astype(jnp.float32), eps)
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _experts(h, w, sizes, control):
    """(the routed layer's part; each row's selection margin: the smaller
    of the gap at the last kept GROUP and the gap at the last chosen
    expert): the held experts one at a time over every row under a dense
    mask of weights, plus the shared expert."""
    k, scale, norm, first, count, shared, groups, kept, eps = sizes
    mm = _mm(control == "float8")
    x = _rms(h, w["ln2"].astype(jnp.float32), eps)
    s = jax.nn.sigmoid(mm(x, w["router"]))                  # [S, E]
    pick = s + w["bias"].astype(jnp.float32)
    gap = jnp.full((s.shape[0],), jnp.inf, jnp.float32)
    if groups > 1 and control != "plain_topk":
        grouped = pick.reshape(s.shape[0], groups, -1)
        top = jnp.sort(grouped, axis=-1)[..., ::-1]
        score = top[..., 0] if control == "group_max" \
            else top[..., :2].sum(-1)
        ranked = jnp.sort(score, axis=-1)[:, ::-1]
        # exact: the lower group of a tie is kept (a stable sort)
        order = jnp.argsort(-score, axis=-1, stable=True)[:, :kept]
        keep = jnp.zeros(score.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], order].set(True)
        pick = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(s.shape)
        if kept < groups:
            gap = ranked[:, kept - 1] - ranked[:, kept]
    ranked = jnp.sort(pick, axis=-1)[:, ::-1]
    order = jnp.argsort(-pick, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros(pick.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    wts = jnp.where(chosen, s, 0.0)
    if norm:
        wts = wts / (wts.sum(-1, keepdims=True) + 1e-20)
    wts = wts * scale
    ids = first + jnp.arange(count)

    def one(r, e):
        wg, wu, wd, we = e
        return r + we[:, None] * mm(jax.nn.silu(mm(x, wg)) * mm(x, wu),
                                    wd), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["gate"], w["up"], w["down"], wts[:, ids].T))
    if shared:
        r = r + mm(jax.nn.silu(mm(x, w["shared_gate"]))
                   * mm(x, w["shared_up"]), w["shared_down"])
    return r, jnp.minimum(gap, ranked[:, k - 1] - ranked[:, k])


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(h_rows, ln_f, head, eps, lower):
    return _mm(lower)(_rms(h_rows, ln_f.astype(jnp.float32), eps), head)


def is_kda(config, i):
    return (i + 1) % config["layer_group_size"] != 0


def forward(params, config, tokens, rows, name="lng", held=None,
            control=None, stats=None, probes=None, pad_to=1024):
    """(logits [len(rows), V held] as numpy float32, margin [S], what the
    probes read [KDA layers, M, H, D] or None) for the sequence
    ``tokens`` [S]: the next-token logits after each position in
    ``rows``, every position's smallest routing selection margin over the
    routed layers, and, with ``probes`` [M, H, D], what they read in
    every KDA layer's state after the last position.  ``config`` holds
    the source's keys, ``num_experts`` the ROUTER's width; ``held``
    (first, count) says which experts the leaves hold (all, by default).
    ``stats`` (a dict) receives, a layer, the RMS of the residual and of
    its two parts, and the logits' standard deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    eps = float(c["rms_norm_eps"])
    lower = control == "float8"
    H, D = c["num_attention_heads"], c["head_dim"]
    first, count = held or (0, c["num_experts"])
    moe_sizes = (c["num_experts_per_tok"],
                 float(c.get("routed_scaling_factor", 1.0)),
                 bool(c.get("norm_topk_prob", True)), int(first), int(count),
                 bool(c.get("moe_shared_expert_intermediate_size", 0)),
                 int(c.get("n_group", 1)), int(c.get("topk_group", 1)), eps)
    kda_sizes = (H, D, int(c["short_conv_kernel_size"]),
                 float(c["kda_lower_bound"]), eps)
    mla_sizes = (H, c["kv_lora_rank"], c["qk_nope_head_dim"],
                 c["qk_rope_head_dim"], c["v_head_dim"],
                 float(c["rope_theta"]), eps)
    n = len(tokens)
    step = pad_to if n > pad_to else ROW_BLOCK if n > ROW_BLOCK else n
    padded = np.zeros(-(-n // step) * step, np.int32)
    padded[:n] = np.asarray(tokens, np.int32)
    tokens = jnp.asarray(padded)
    S = tokens.shape[0]
    rms_of = lambda a: float(np.sqrt(np.mean(np.square(    # noqa: E731
        np.asarray(a)[:n]))))
    ask = jnp.zeros((1, H, D), jnp.float32) if probes is None \
        else jnp.asarray(probes, jnp.float32)
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    margin = np.full(S, np.inf, np.float32)
    answered = []
    L = c["num_hidden_layers"]
    order = list(range(L))
    if control == "mla_at_4":
        order[-2:] = order[:-3:-1]
    of_mixer = None if control in ("plain_topk", "group_max") else control
    for i in order:
        us = f"{name}_h{i}"
        if is_kda(c, i):
            w = {"ln1": params[f"{us}_ln1_scale"],
                 **{k: params[f"{us}_kda_{leaf}"] for k, leaf in (
                     ("qkv", "qkv_weight"), ("conv", "conv_weight"),
                     ("f", "f_weight"), ("dt_bias", "dt_bias"),
                     ("A_log", "A_log"), ("beta", "beta_weight"),
                     ("gate", "gate_weight"), ("norm", "norm_scale"),
                     ("out", "out_weight"))}}
            part, read = _kda(h, w, n, ask, kda_sizes, of_mixer)
            answered.append(np.asarray(read))
        else:
            w = {"ln1": params[f"{us}_ln1_scale"],
                 **{k: params[f"{us}_attn_{leaf}"] for k, leaf in (
                     ("q", "q_weight"), ("kv_a", "kv_a_weight"),
                     ("kv_a_norm", "kv_a_norm_scale"),
                     ("kv_b", "kv_b_weight"), ("gate", "gate_weight"),
                     ("proj", "proj_weight"))}}
            part = _mla(h, w, mla_sizes, of_mixer)
        layer = {"kind": "kda" if is_kda(c, i) else "mla",
                 "residual": rms_of(h), "mixer": rms_of(part)} \
            if stats is not None else None
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
                h, we, moe_sizes, control if control in (
                    "float8", "plain_topk", "group_max") else None)
            margin = np.minimum(margin, np.asarray(gap))
        if stats is not None:
            layer["ffn"] = rms_of(part)
            stats.setdefault("layers", []).append(layer)
        h = h + part
    want = np.full(-(-len(rows) // ROWS_PAD) * ROWS_PAD, rows[-1], np.int32)
    want[:len(rows)] = rows
    logits = np.asarray(_head(
        h[jnp.asarray(want)], params[f"{name}_ln_f_scale"],
        params[f"{name}_lm_head_weight"], eps, lower))[:len(rows)]
    if stats is not None:
        stats["logits"] = float(logits.std())
    return logits, margin[:n], \
        None if probes is None else np.stack(answered)
