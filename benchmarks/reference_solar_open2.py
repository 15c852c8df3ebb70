"""Solar-Open2's layer stack in plain float32 ``jax.numpy``, precision
``highest``: the benchmark's own copy of the reference (the equations of
``hetu_tpu/models/reference_kda_gqa.py``, written again here and not
imported: the yardstick must not move with the program), laid out so that
a 25,000-position request fits on the chip beside 6.6 GB of served
weights.  It decides ``correct``.  No cache, no kernels, no batching, no
chunks: the KDA recurrence STEP BY STEP (a scan over positions), the
grouped-query layer a full causal softmax over every position.

``h`` the residual, ``u = RMSNorm(h)`` (eps 1e-5), ``d`` the hidden
width, ``H`` heads of ``D``, ``Hkv`` K/V heads.

KDA layer (every layer whose number is not in ``gqa_layers``):

    [q~ | k~ | v~] = u W_qkv; every column through the causal conv of K
    taps (zeros before the sequence, no bias), then SiLU
    q = l2(q~) D^-1/2, k = l2(k~) a head (l2 x = x rsqrt(sum x^2 + 1e-6))
    g_t = -exp(A_log_h) softplus(u W_f_a W_f_b + dt_bias)      a channel
    beta_t = 2 sigmoid(u W_beta)                               a head
    S' = Diag(exp g_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T q_t
    y = RMSNorm_D(o_t) scale sigmoid(u W_g_a W_g_b + b_g)      a channel
    h <- h + concat(y) W_o

GQA layer: q = u W_q (H x D), k = u W_k, v = u W_v (Hkv x D), nothing
rotated, no q/k norm, no biases; causal softmax(q k^T D^-1/2), query head
n reading K/V head n // (H / Hkv); times sigmoid(u W_gate) a channel;
W_o.

FFN (every layer): s = sigmoid(x W_r) over ALL ``n_routed_experts``, the
``num_experts_per_tok`` largest s + b chosen, weights s at the chosen
normalised over all the chosen, scaled; the HELD experts' part (``held`` =
(first, count)) and the shared expert.  Head: ``RMSNorm(h) W_head`` over
the rows held.

Departures from the published description: none in the mathematics; the
sizes the row does not give (the low rank = ``head_dim``, one bias on the
gate's second projection, the router's scoring) are the configuration
file's ``assumed``.

``control`` computes something else ON PURPOSE (each has to come out not
correct): "beta_one" (beta not doubled), "safe_gate" (``g = -5
sigmoid(exp(A_log) (f + dt_bias))``: the bounded gate of the sibling
family), "gate_head" (the KDA gate one a head: the mean of the head's
pre-activations), "no_gate" (the GQA gate off), "rope" (the GQA layer's q
and k rotated, rotate-half, ``rope_theta``), "gqa_at_3" (the GQA layer
last of the period instead of first), "conv_cut" (the conv's history
dropped at every multiple of ``CONV_CUT`` positions: tails not carried
from one prompt chunk to the next), "float8" (the operands of every
weight product rounded to float8 e4m3), "no_decay" (alpha 1), "no_delta"
(``S' + beta k v^T``).

What differs from the program's copy is only how the work is cut: the
sequence is padded to a multiple of ``pad_to`` (causal; a padded position
moves no state: its ``g``, ``beta`` and ``k`` are 0) and the wanted rows
to a multiple of ``ROWS_PAD``; a KDA layer takes its heads ``KDA_HEADS``
at a time (front end and scan alike), the attention one K/V head's query
heads at a time and inside it the query rows ``ROW_BLOCK`` at a time;
every part of every layer is one jitted call whose weights are upcast
inside it, the held experts one at a time by a ``lax.scan``.  ``probes``
[M, H, D] (unit-scale queries) are answered by every KDA layer's state
after the sequence's last real position: ``S^T r``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 64
KDA_HEADS = 16
ROWS_PAD = 64
CONV_CUT = 256
CONTROLS = ("beta_one", "safe_gate", "gate_head", "no_gate", "rope",
            "gqa_at_3", "conv_cut", "float8", "no_decay", "no_delta")
HIGHEST = jax.lax.Precision.HIGHEST


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _rope(x, theta):
    """x [S, heads, d] at positions 0..S-1, rotate-half over d."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[:, None, :]
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
    state after position ``n - 1``: [M, H, D]), ``KDA_HEADS`` heads at a
    time."""
    H, D, K, rank, beta_scale, eps = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S, d = h.shape
    hb = min(KDA_HEADS, H)
    nb = H // hb
    real = jnp.arange(S) < n
    pos = jnp.arange(S)
    u = _rms(h, f32(w["ln1"]), eps)
    # the low rank's first steps, every head's alike
    fa = mm(u, w["f_a"]) if rank else u
    ga = mm(u, w["gate_a"]) if rank else u
    taps = f32(w["conv"]).reshape(K, 3, nb, hb * D).transpose(2, 0, 1, 3)

    def by_block(leaf, rows):
        """[rows, H D] -> [blocks, rows, hb D]."""
        return leaf.reshape(rows, nb, hb * D).transpose(1, 0, 2)

    parts = (
        w["qkv"].reshape(d, 3, nb, hb * D).transpose(2, 0, 1, 3), taps,
        by_block(w["f_b"], fa.shape[1]), by_block(w["gate_b"], ga.shape[1]),
        f32(w["dt_bias"]).reshape(nb, hb * D),
        f32(w["gate_bias"]).reshape(nb, hb * D),
        f32(w["A_log"]).reshape(nb, hb),
        w["beta"].reshape(d, nb, hb).transpose(1, 0, 2),
        f32(w["norm"]).reshape(nb, hb, D),
        jnp.moveaxis(probes.reshape(-1, nb, hb, D), 1, 0))

    def heads(part):
        (w_qkv, tap, w_f, w_g, dt_bias, g_bias, a_log, w_beta, scale,
         ask) = part
        x = mm(u, w_qkv.reshape(d, 3 * hb * D)).reshape(S, 3, hb * D)
        y = 0.0
        for j in range(K):
            back = K - 1 - j
            shifted = jnp.pad(x, ((back, 0), (0, 0), (0, 0)))[:S]
            if control == "conv_cut":
                shifted = jnp.where((pos % CONV_CUT >= back)[:, None, None],
                                    shifted, 0.0)
            y = y + tap[j] * shifted
        x = jax.nn.silu(y).reshape(S, 3, hb, D)
        q, k, v = _l2(x[:, 0]) * D ** -0.5, _l2(x[:, 1]), x[:, 2]
        a = jnp.repeat(jnp.exp(a_log), D)
        f = mm(fa, w_f) + dt_bias
        g = -5.0 * jax.nn.sigmoid(a * f) if control == "safe_gate" \
            else -a * jax.nn.softplus(f)
        if control == "no_decay":
            g = jnp.zeros_like(g)
        beta = jax.nn.sigmoid(mm(u, w_beta))                # [S, hb]
        if control != "beta_one":
            beta = beta_scale * beta
        # a padded position moves nothing
        g = jnp.where(real[:, None], g, 0.0).reshape(S, hb, D)
        beta = jnp.where(real[:, None], beta, 0.0)
        k = jnp.where(real[:, None, None], k, 0.0)

        def step(St, x):
            qt, kt, vt, gt, bt = x
            St = St * jnp.exp(gt)[..., None]
            r = vt if control == "no_delta" else vt - jnp.einsum(
                "hk,hkv->hv", kt, St, precision=HIGHEST)
            St = St + (bt[:, None] * kt)[..., None] * r[:, None, :]
            return St, jnp.einsum("hk,hkv->hv", qt, St, precision=HIGHEST)

        St, o = jax.lax.scan(step, jnp.zeros((hb, D, D), jnp.float32),
                             (q, k, v, g, beta))
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) * scale
        z = mm(ga, w_g)
        if rank:
            z = z + g_bias
        z = z.reshape(S, hb, D)
        if control == "gate_head":
            z = jnp.broadcast_to(z.mean(-1, keepdims=True), z.shape)
        o = o * jax.nn.sigmoid(z)
        return o.reshape(S, hb * D), jnp.einsum(
            "mhk,hkv->mhv", ask, St, precision=HIGHEST)

    o, read = jax.lax.map(heads, parts)        # [nb, S, hb D], [nb, M, ..]
    o = o.transpose(1, 0, 2).reshape(S, H * D)
    read = jnp.moveaxis(read, 0, 1).reshape(-1, H, D)
    return mm(o, w["out"]), read


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _gqa(h, w, sizes, control):
    """The grouped-query layer's part [S, d], one K/V head's query heads
    at a time, ``ROW_BLOCK`` query rows at a time."""
    H, Hkv, D, gated, theta, eps = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S, d = h.shape
    group = H // Hkv
    u = _rms(h, f32(w["ln1"]), eps)
    block = min(ROW_BLOCK, S)
    pos = jnp.arange(S)
    rot = (lambda a: _rope(a, theta)) if control == "rope" \
        else (lambda a: a)

    def by_kv_head(leaf, width):
        return leaf.reshape(d, Hkv, width).transpose(1, 0, 2)

    parts = (by_kv_head(w["q"], group * D), by_kv_head(w["k"], D),
             by_kv_head(w["v"], D), by_kv_head(w["gate"], group * D))

    def heads(part):
        wq, wk, wv, wg = part
        q = rot(mm(u, wq).reshape(S, group, D))
        k = rot(mm(u, wk).reshape(S, 1, D))[:, 0]
        v = mm(u, wv)

        def rows(r0):
            sc = jnp.einsum(
                "qhd,sd->hqs", jax.lax.dynamic_slice_in_dim(q, r0, block),
                k, precision=HIGHEST, preferred_element_type=jnp.float32)
            live = pos[None, :] <= (r0 + jnp.arange(block))[:, None]
            p = jax.nn.softmax(
                jnp.where(live[None], sc * D ** -0.5, -jnp.inf), -1)
            return jnp.einsum("hqs,sd->qhd", p, v, precision=HIGHEST)

        o = jax.lax.map(rows, jnp.arange(0, S, block)).reshape(
            S, group * D)
        if gated and control != "no_gate":
            o = o * jax.nn.sigmoid(mm(u, wg))
        return o

    o = jax.lax.map(heads, parts)                          # [Hkv, S, g D]
    return mm(o.transpose(1, 0, 2).reshape(S, H * D), w["proj"])


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _experts(h, w, sizes, control):
    """(the routed layer's part; each row's selection margin: the gap at
    the last chosen expert): the held experts one at a time over every
    row under a dense mask of weights, plus the shared expert."""
    k, scale, first, count, shared, eps = sizes
    mm = _mm(control == "float8")
    x = _rms(h, w["ln2"].astype(jnp.float32), eps)
    s = jax.nn.sigmoid(mm(x, w["router"]))                  # [S, E]
    pick = s + w["bias"].astype(jnp.float32)
    ranked = jnp.sort(pick, axis=-1)[:, ::-1]
    # exact: the lower expert of a tie is chosen (a stable sort)
    order = jnp.argsort(-pick, axis=-1, stable=True)[:, :k]
    chosen = jnp.zeros(pick.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], order].set(True)
    wts = jnp.where(chosen, s, 0.0)
    wts = wts / (wts.sum(-1, keepdims=True) + 1e-20) * scale
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
    return r, ranked[:, k - 1] - ranked[:, k]


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(h_rows, ln_f, head, eps, lower):
    return _mm(lower)(_rms(h_rows, ln_f.astype(jnp.float32), eps), head)


def is_kda(config, i):
    return i not in config["gqa_layers"]


def forward(params, config, tokens, rows, name="slr", held=None,
            control=None, stats=None, probes=None, pad_to=1024):
    """(logits [len(rows), V held] as numpy float32, margin [S], what the
    probes read [KDA layers, M, H, D] or None) for the sequence
    ``tokens`` [S]: the next-token logits after each position in
    ``rows``, every position's smallest routing selection margin over the
    layers, and, with ``probes`` [M, H, D], what they read in every KDA
    layer's state after the last position (in the layers' own order,
    whatever order a control runs them in).  ``config`` holds the
    source's keys, ``n_routed_experts`` the ROUTER's width; ``held``
    (first, count) says which experts the leaves hold (all, by default).
    ``stats`` (a dict) receives, a layer, the RMS of the residual and of
    its two parts, and the logits' standard deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    eps = float(c["rms_norm_eps"])
    lower = control == "float8"
    H, D = c["num_attention_heads"], c["head_dim"]
    first, count = held or (0, c["n_routed_experts"])
    rank = 0 if c.get("kda_use_full_proj") else D
    moe_sizes = (c["num_experts_per_tok"],
                 float(c.get("routed_scaling_factor", 1.0)), int(first),
                 int(count), bool(c.get("n_shared_experts", 0)), eps)
    kda_sizes = (H, D, int(c["linear_attn_config"]["short_conv_kernel_size"]),
                 rank, 2.0 if c.get("kda_allow_neg_eigval") else 1.0, eps)
    gqa_sizes = (H, c["num_key_value_heads"], D,
                 bool(c.get("use_gqa_gate")), float(c["rope_theta"]), eps)
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
    answered = {}
    L = c["num_hidden_layers"]
    order = list(range(L))
    if control == "gqa_at_3":
        # every period's GQA layer behind its KDA layers
        order = [i for i in order if is_kda(c, i)] \
            + [i for i in order if not is_kda(c, i)]
    for i in order:
        us = f"{name}_h{i}"
        if is_kda(c, i):
            low = ("f_a", "f_a_weight"), ("f_b", "f_b_weight"), \
                ("gate_a", "gate_a_weight"), ("gate_b", "gate_b_weight"), \
                ("gate_bias", "gate_bias")
            full = ("f_b", "f_weight"), ("gate_b", "gate_weight")
            w = {"ln1": params[f"{us}_ln1_scale"],
                 **{k: params[f"{us}_kda_{leaf}"] for k, leaf in (
                     ("qkv", "qkv_weight"), ("conv", "conv_weight"),
                     ("dt_bias", "dt_bias"), ("A_log", "A_log"),
                     ("beta", "beta_weight"), ("norm", "norm_scale"),
                     ("out", "out_weight")) + (low if rank else full)}}
            if not rank:
                w.update(f_a=None, gate_a=None,
                         gate_bias=jnp.zeros((H * D,), jnp.float32))
            part, read = _kda(h, w, n, ask, kda_sizes, control)
            answered[i] = np.asarray(read)
        else:
            w = {"ln1": params[f"{us}_ln1_scale"],
                 **{k: params[f"{us}_attn_{leaf}"] for k, leaf in (
                     ("q", "q_weight"), ("k", "k_weight"),
                     ("v", "v_weight"), ("proj", "proj_weight"))},
                 # (an ungated layer hands the query's leaf in the
                 # gate's place: same shape, not read)
                 "gate": params.get(f"{us}_attn_gate_weight",
                                    params[f"{us}_attn_q_weight"])}
            part = _gqa(h, w, gqa_sizes, control)
        layer = {"kind": "kda" if is_kda(c, i) else "gqa",
                 "residual": rms_of(h), "mixer": rms_of(part)} \
            if stats is not None else None
        h = h + part
        we = {"ln2": params[f"{us}_ln2_scale"],
              "router": params[f"{us}_moe_router_weight"],
              "bias": params[f"{us}_moe_router_bias"],
              "gate": params[f"{us}_moe_experts_gate"],
              "up": params[f"{us}_moe_experts_up"],
              "down": params[f"{us}_moe_experts_down"]}
        if moe_sizes[4]:
            we.update(
                shared_gate=params[f"{us}_moe_shared_gate_weight"],
                shared_up=params[f"{us}_moe_shared_up_weight"],
                shared_down=params[f"{us}_moe_shared_down_weight"])
        part, gap = _experts(h, we, moe_sizes,
                             control if control == "float8" else None)
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
        None if probes is None else np.stack(
            [answered[i] for i in sorted(answered)])
