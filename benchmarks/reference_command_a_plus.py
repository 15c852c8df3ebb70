"""command-a-plus-05-2026's block stack in plain float32 ``jax.numpy``,
precision ``highest``: the benchmark's own copy of the reference (the
equations of ``hetu_tpu/models/reference_parallel_moe.py``, written again
here and not imported: the yardstick must not move with the program),
laid out so that a 16,896-position request fits on the chip beside 9.5 GB
of served weights.  It decides ``correct``.  The leaves are the PUBLISHED
layout; the rotation is the published one (interleaved pairs).

``h`` the residual, ``d`` 4096, ``H`` 128 query heads over ``Hkv`` 8 K/V
heads of ``dh`` 128, ``W`` 4096, eps 1e-5.  Every layer ``l``:

    x   = LN(h; g_l)      LN(u; g) = g (u - mean u) / sqrt(var u + eps)
    q   = x W_q [H, dh]   k = x W_k [Hkv, dh]   v = x W_v [Hkv, dh]
    sliding layer:  q, k <- RoPE(q), RoPE(k): INTERLEAVED pairs (2j, 2j+1)
                    over the whole head, theta 50,000, no scaling; query
                    t admits t - W < j <= t
    full layer:     NO rotation; every j <= t
    o_n = sum_j softmax_j(q_n . k_{n // 16, j} / sqrt(dh)) v_{n // 16, j}
    a   = concat_n(o_n) W_o
    s   = sigmoid(float32(x) W_r) [128];  S = the 8 largest of s;
    w_e = s_e / (sum_{S} s + 1e-20)      (no selection bias)
    E(u; G, U, D) = (silu(u G) * (u U)) D
    f   = sum_{e in S, e held} w_e E(x; e) + (1/4) sum_{j<4} E(x; shared j)
    h  <- h + a + f                       (ONE norm, ONE addition)

    logits = logit_scale * LN(h; g_final) T^T   (T the held rows)

What differs from the program's copy is only how the work is cut: the
attention is taken a K/V head at a time (its 16 query heads' columns of
``W_q`` and rows of ``W_o``), the query rows ``ROW_BLOCK`` at a time
against ALL keys with the explicit band, the held experts and the four
shared experts one at a time over every row with a dense weight, a leaf
cast to float32 inside the jitted call that reads it, and the head for
the answer's rows only.  An expert is ``reference_glm47flash``'s own
function (the same equation; the benchmark's code, not the program's).

``control`` computes ONE thing differently, for the probe and the tests
(the run never passes one): the comparison has to call each not correct.
"float8" rounds the operands of every weight product to float8 (e4m3),
the nearest precision below the bfloat16 the configuration states, and
computes the router's product and the attention's softmax in bfloat16;
"sequential" norms ``h + a`` again (the same scale) before the FFN;
"rotate_full" rotates the full layers too; "unrotated_sliding" rotates
nothing; "window_2048" / "window_8192" change the band; "shared_sum" adds
the four shared experts unaveraged; "norm_held" normalises the weights
over the held chosen experts alone; "rmsnorm" takes no mean.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_glm47flash import _add_expert, _mm

ROW_BLOCK = 256
CONTROLS = ("float8", "sequential", "rotate_full", "unrotated_sliding",
            "window_2048", "window_8192", "shared_sum", "norm_held",
            "rmsnorm")


def _ln(u, g, eps, centre=True):
    if centre:
        u = u - u.mean(-1, keepdims=True)
    return g.astype(jnp.float32) * u * jax.lax.rsqrt(
        (u * u).mean(-1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("eps", "centre"))
def _norm(h, g, eps, centre):
    return _ln(h, g, eps, centre)


def _rotate(x, theta):
    """x [S, heads, dh] at positions 0..S-1, interleaved pairs."""
    S, dh = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dh", "theta", "window",
                                             "lower"), donate_argnums=(0,))
def _add_kv_head(a, x, wq, wk, wv, wo, dh, theta, window, lower):
    """a + the part of the attention that ONE K/V head's query heads
    give: ``wq`` [d, G dh] their columns, ``wo`` [G dh, d] their rows.
    ``theta`` 0.0: no rotation; ``window`` 0: causal, else the band."""
    mm = _mm(lower)
    low = jnp.bfloat16 if lower else jnp.float32
    S = x.shape[0]
    q = mm(x, wq).reshape(S, -1, dh)                       # [S, G, dh]
    k = mm(x, wk).reshape(S, 1, dh)
    v = mm(x, wv)                                          # [S, dh]
    if theta:
        q, k = _rotate(q, theta), _rotate(k, theta)
    k = k[:, 0]
    block = min(ROW_BLOCK, S)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block, 0)
        s = jnp.einsum("qgd,sd->gqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        i = (r0 + jnp.arange(block))[:, None]
        j = jnp.arange(S)[None, :]
        seen = j <= i
        if window:
            seen &= j > i - window
        p = jax.nn.softmax(
            jnp.where(seen[None], s * dh ** -0.5, -jnp.inf).astype(low),
            -1).astype(jnp.float32)
        return jnp.einsum("gqs,sd->qgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(rows, jnp.arange(0, S, block)).reshape(S, -1)
    return a + mm(o, wo)


@functools.partial(jax.jit, static_argnames=("k", "norm", "first", "count",
                                             "norm_held", "lower"))
def _route(x, w_router, k, norm, first, count, norm_held, lower):
    """(dense weights [S, E], each row's selection margin): sigmoid
    scores over all the experts, the ``k`` largest chosen, normalised
    over all the chosen (``norm_held``: over the held ones alone)."""
    if lower:
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.bfloat16), w_router.astype(jnp.bfloat16)
        ).astype(jnp.float32))
    else:
        s = jax.nn.sigmoid(jnp.dot(x, w_router.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
    ranked = jnp.sort(s, axis=-1)[:, ::-1]
    w = jnp.where(s >= ranked[:, k - 1:k], s, 0.0)
    if norm_held:
        e = jnp.arange(s.shape[1])
        w = jnp.where((e >= first) & (e < first + count), w, 0.0)
    if norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w, ranked[:, k - 1] - ranked[:, k]


@functools.partial(jax.jit, static_argnames=("eps", "centre", "scale",
                                             "lower"))
def _head(h_rows, ln_f, table, eps, centre, scale, lower):
    return scale * _mm(lower)(_ln(h_rows, ln_f, eps, centre), table.T)


@jax.jit
def _rms_of(x):
    return jnp.sqrt((x * x).mean())


def forward(params, config, tokens, rows, name="cmd", held=None,
            control=None, stats=None):
    """(logits [len(rows), V] as numpy float32, margin [S]) for the
    sequence ``tokens`` [S] (``S`` a multiple of ``ROW_BLOCK`` or below
    it): the next-token logits over the held rows after each position in
    ``rows``, and every position's smallest selection margin over the
    layers (the last chosen sigmoid score against the first not chosen).
    ``config`` holds the source's keys with ``num_experts`` the ROUTER's
    width; ``held`` (first, count) the experts the leaves hold.
    ``stats`` (a dict) receives, a layer, the RMS of the residual, of the
    attention's part and of the FFN's, and the logits' deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    H, Hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    G = H // Hkv
    eps, theta = float(c["layer_norm_eps"]), float(c["rope_theta"])
    E, k, f = (c["num_experts"], c["num_experts_per_tok"],
               c["intermediate_size"])
    first, count = held or (0, E)
    n_shared = c["num_shared_experts"]
    lower = control == "float8"
    centre = control != "rmsnorm"
    window_of = {"window_2048": 2048, "window_8192": 8192}.get(
        control, int(c["sliding_window"]))
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    margin = np.full(S, np.inf, np.float32)
    share = jnp.full((S,), 1.0 if control == "shared_sum"
                     else 1.0 / n_shared, jnp.float32)
    layers = []
    for i, kind in enumerate(c["layer_types"][:c["num_hidden_layers"]]):
        us = f"{name}_h{i}"
        g = params[f"{us}_ln1_scale"]
        sliding = kind == "sliding_attention"
        rotate = (sliding and control != "unrotated_sliding") \
            or (not sliding and control == "rotate_full")
        x = _norm(h, g, eps, centre)
        wq, wk, wv, wo = (params[f"{us}_attn_{n}_weight"]
                          for n in ("q", "k", "v", "proj"))
        a = jnp.zeros_like(h)
        for kv in range(Hkv):
            qs, ks = slice(kv * G * dh, (kv + 1) * G * dh), \
                slice(kv * dh, (kv + 1) * dh)
            a = _add_kv_head(a, x, wq[:, qs], wk[:, ks], wv[:, ks], wo[qs],
                             dh, theta if rotate else 0.0,
                             window_of if sliding else 0, lower)
        xf = _norm(h + a, g, eps, centre) if control == "sequential" else x
        w, m = _route(xf, params[f"{us}_moe_router_weight"], k,
                      bool(c.get("norm_topk_prob", True)), int(first),
                      int(count), control == "norm_held", lower)
        margin = np.minimum(margin, np.asarray(m))
        y = jnp.zeros_like(h)
        gate, up, down = (params[f"{us}_moe_experts_{n}"]
                          for n in ("gate", "up", "down"))
        for e in range(count):
            y = _add_expert(y, xf, w[:, first + e], gate[e], up[e], down[e],
                            lower)
        sg, su, sd = (params[f"{us}_moe_shared_{n}_weight"]
                      for n in ("gate", "up", "down"))
        for j in range(n_shared):
            cols = slice(j * f, (j + 1) * f)
            y = _add_expert(y, xf, share, sg[:, cols], su[:, cols], sd[cols],
                            lower)
        if stats is not None:
            layers.append([kind, float(_rms_of(h)), float(_rms_of(a)),
                           float(_rms_of(y))])
        h = h + a + y
    h_rows = h[jnp.asarray(rows, jnp.int32)]
    logits = np.asarray(_head(
        h_rows, params[f"{name}_ln_f_scale"], params[f"{name}_wte_table"],
        eps, centre, float(c.get("logit_scale", 1.0)), lower))
    if stats is not None:
        stats["layers"] = layers
        stats["logits"] = float(logits.std())
    return logits, margin
