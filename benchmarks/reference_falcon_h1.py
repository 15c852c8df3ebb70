"""Falcon-H1's block stack in plain float32 ``jax.numpy``, precision
``highest``: the benchmark's own copy of the reference (the equations of
``hetu_tpu/models/reference_ssm_hybrid.py``, written again here and not
imported: the yardstick must not move with the program), laid out so
that a 2,048-token sequence fits on the chip beside 10.5 GB of served
weights.  It decides ``correct``.

The equations are the family's public ones (``falcon_h1``): no cache, no
state carried in, no batching, one sequence at a time.  With ``u`` the
RMSNorm of the residual ``h`` (no biases but the conv's):

  h <- h + ssm_out * Mixer(ssm_in * u) + attention_out * Attn(attention_in * u)
  h <- h + MLP(RMSNorm(h))

  Attn    q = x W_q (20 heads of 128), k = x W_k * key_multiplier,
          v = x W_v (4 heads); rotate-half RoPE over all 128 columns,
          theta 1e11, angles in float32; causal softmax(q k^T / sqrt(128)) v,
          query head n reading K/V head n // 5; W_o
  Mixer   [z | xBC | dt] = (x W_in) * mup, widths 4096 | 5120 | 32, the
          five ssm_multipliers over the slices z, x, B, C, dt;
          xBC <- silu(conv1d(xBC) + b), depthwise, causal, 4 taps, zeros
          before the sequence; x (32 heads of 128), B, C (2 groups of
          256, a group serving 16 heads); dt <- softplus(dt + dt_bias);
          A = -exp(A_log) a head;
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T  ([128, 256] a head),
          y_t = S_t C_t + D x_t, as a ``lax.scan`` over POSITIONS (not
          the chunked form); y <- RMSNorm over 2 groups of
          (y * silu(z)) (the gate first); W_out
  MLP     (silu(gate_multiplier * x W_gate) * x W_up) W_down * down_multiplier
  model   embedding * embedding_multiplier; final RMSNorm; untied head;
          logits * lm_head_multiplier

What differs from the program's copy is only how the work is cut: every
branch of every layer is one jitted call whose weights are upcast inside
it, the query rows of attention are taken ``ROW_BLOCK`` at a time, and
the head runs over ``VOCAB_BLOCK`` columns at a time for the answer's
rows only.  ``_mm``, ``_rms`` and ``_head`` are
``reference_glm47flash``'s own (the same equations; the benchmark's code,
not the program's).  Departures from the family's public code: ``dt`` is
not clamped (its ``time_step_limit`` is (0, inf)).

Besides the logits it returns every layer's matrix state after the last
real position, which the runner sets against the state the engine left
in the slot (``runners/serve_ssm_hybrid.py``: the state's own check).

``control`` computes something else ON PURPOSE, each of which the
comparison has to call not correct (``probe_falcon_h1_check.py``):
"float8" rounds the operands of every weight product to float8 (e4m3),
the nearest precision below the bfloat16 the configuration states;
"ssm" / "attention" leave that branch out of the residual; "carry"
zeroes the matrix state at position ``carry_at`` (a chunk boundary);
"position" rotates the keys one position on from the queries; and
"state_bf16" rounds the matrix state to bfloat16 after every step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_glm47flash import _head, _mm, _rms

ROW_BLOCK = 256
VOCAB_BLOCK = 16320          # 261,120 / 16
CONTROLS = ("float8", "ssm", "attention", "carry", "position", "state_bf16")


def _rope(x, theta, shift=0):
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(S, dtype=jnp.float32) + shift)[:, None] * inv
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _mean_sq(a):
    return jnp.mean(a * a)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _attention(u, w, sizes, control):
    """(attention_out * Attn(attention_in * u), the scores' mean
    square), rows in blocks of ``ROW_BLOCK``."""
    H, Hkv, dh, theta, m_in, m_key, m_out = sizes
    mm = _mm(control == "float8")
    S = u.shape[0]
    x = u * m_in
    q = _rope(mm(x, w["q"]).reshape(S, H, dh), theta)
    k = _rope((mm(x, w["k"]) * m_key).reshape(S, Hkv, dh), theta,
              1 if control == "position" else 0)
    v = mm(x, w["v"]).reshape(S, Hkv, dh)
    q = q.reshape(S, Hkv, H // Hkv, dh)            # head n = (n // g, n % g)
    block = min(ROW_BLOCK, S)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block, 0)
        s = jnp.einsum("qhgd,shd->hgqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        live = jnp.arange(S)[None, :] <= (r0 + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("hgqs,shd->qhgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        return o, jnp.sum(jnp.where(live[None, None], s * s, 0.0)), \
            live.sum() * H

    o, sq, n = jax.lax.map(rows, jnp.arange(0, S, block))
    return mm(o.reshape(S, H * dh), w["proj"]) * m_out, sq.sum() / n.sum()


@functools.partial(jax.jit, static_argnames=("sizes", "control", "carry_at"))
def _mixer(u, w, n, sizes, control, carry_at):
    """(ssm_out * Mixer(ssm_in * u), the matrix state after position
    ``n - 1``): the recurrence position by position; positions from
    ``n`` on (the padding) have ``dt`` 0 and move nothing."""
    Hs, P, N, G, K, eps, m_in, m_out, mup = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S = u.shape[0]
    d_ssm, gn = Hs * P, G * N
    widths = (d_ssm, d_ssm, gn, gn, Hs)
    vec = jnp.concatenate([jnp.full((wd,), m, jnp.float32)
                           for wd, m in zip(widths, mup)])
    proj = mm(u * m_in, w["in"]) * vec
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * gn], -1)
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))               # zeros before 0
    taps = f32(w["taps"])
    xbc = jax.nn.silu(sum(taps[j] * pad[j:j + S] for j in range(K))
                      + f32(w["conv_bias"]))
    xs = xbc[:, :d_ssm].reshape(S, Hs, P)
    Bm = xbc[:, d_ssm:d_ssm + gn].reshape(S, G, N)
    Cm = xbc[:, d_ssm + gn:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + f32(w["dt_bias"]))           # [S, Hs]
    dt = jnp.where(jnp.arange(S)[:, None] < n, dt, 0.0)
    A = -jnp.exp(f32(w["A_log"]))
    hg = Hs // G

    def step(state, row):
        t, xt, bt, ct, dtt = row
        if control == "carry":
            state = jnp.where(t == carry_at, 0.0, state)
        bh, ch = jnp.repeat(bt, hg, axis=0), jnp.repeat(ct, hg, axis=0)
        state = state * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :]
        if control == "state_bf16":
            # (not a pair of casts: the TPU compiler keeps the excess
            # precision of a float32 -> bfloat16 -> float32 round trip)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.sum(state * ch[:, None, :], axis=-1)

    state, y = jax.lax.scan(step, jnp.zeros((Hs, P, N), jnp.float32),
                            (jnp.arange(S), xs, Bm, Cm, dt))
    y = (y + f32(w["D"])[:, None] * xs).reshape(S, d_ssm)
    g = (y * jax.nn.silu(z)).reshape(S, G, d_ssm // G)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    y = g.reshape(S, d_ssm) * f32(w["norm"])
    return mm(y, w["out"]) * m_out, state


@functools.partial(jax.jit, static_argnames=("eps", "m_gate", "m_down",
                                             "lower"))
def _mlp(h, ln2, wg, wu, wd, eps, m_gate, m_down, lower):
    mm = _mm(lower)
    x = _rms(h, ln2.astype(jnp.float32), eps)
    return mm(jax.nn.silu(mm(x, wg) * m_gate) * mm(x, wu), wd) * m_down


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(h, scale, eps):
    return _rms(h, scale.astype(jnp.float32), eps)


def forward(params, config, tokens, rows, n=None, name="fh1", control=None,
            carry_at=0, stats=None):
    """(logits [len(rows), V] as numpy float32, states [layers, Hs, P, N]
    as numpy float32) for the sequence ``tokens`` [S] (``S`` a multiple
    of ``ROW_BLOCK`` or below it) of which the first ``n`` are real (all,
    by default): the next-token logits after each position in ``rows``
    and every layer's matrix state after position ``n - 1``.  ``config``
    holds the source's keys.  ``stats`` (a dict) receives, a layer, the
    RMS of the residual, of each branch's contribution and of the
    scores, and the logits' standard deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    eps = float(c["rms_norm_eps"])
    lower = control == "float8"
    attn_sizes = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"], float(c["rope_theta"]),
                  float(c["attention_in_multiplier"]),
                  float(c["key_multiplier"]),
                  float(c["attention_out_multiplier"]))
    ssm_sizes = (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                 c["mamba_n_groups"], c["mamba_d_conv"], eps,
                 float(c["ssm_in_multiplier"]),
                 float(c["ssm_out_multiplier"]),
                 tuple(float(m) for m in c["ssm_multipliers"]))
    m_gate, m_down = (float(m) for m in c["mlp_multipliers"])
    tokens = jnp.asarray(tokens, jnp.int32)
    n = jnp.int32(tokens.shape[0] if n is None else n)
    real = np.arange(tokens.shape[0]) < int(n)
    rms_of = lambda a: float(np.sqrt(                      # noqa: E731
        np.mean(np.square(np.asarray(a)[real]))))
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32) \
        * float(c["embedding_multiplier"])
    states = []
    for i in range(c["num_hidden_layers"]):
        us = f"{name}_h{i}"
        u = _norm(h, params[f"{us}_ln1_scale"], eps)
        a, score_sq = _attention(u, {
            "q": params[f"{us}_attn_q_weight"],
            "k": params[f"{us}_attn_k_weight"],
            "v": params[f"{us}_attn_v_weight"],
            "proj": params[f"{us}_attn_proj_weight"]}, attn_sizes, control)
        m, state = _mixer(u, {
            "in": params[f"{us}_ssm_in_weight"],
            "taps": params[f"{us}_ssm_conv_weight"],
            "conv_bias": params[f"{us}_ssm_conv_bias"],
            "dt_bias": params[f"{us}_ssm_dt_bias"],
            "A_log": params[f"{us}_ssm_A_log"], "D": params[f"{us}_ssm_D"],
            "norm": params[f"{us}_ssm_norm_scale"],
            "out": params[f"{us}_ssm_out_weight"]}, n, ssm_sizes, control,
            int(carry_at))
        states.append(np.asarray(state))
        layer = None
        if stats is not None:
            layer = {"residual": rms_of(h), "attention": rms_of(a),
                     "ssm": rms_of(m), "scores": float(np.sqrt(score_sq))}
            stats.setdefault("layers", []).append(layer)
        if control != "attention":
            h = h + a
        if control != "ssm":
            h = h + m
        f = _mlp(h, params[f"{us}_ln2_scale"],
                 params[f"{us}_ffn_gate_weight"],
                 params[f"{us}_ffn_up_weight"],
                 params[f"{us}_ffn_down_weight"], eps, m_gate, m_down, lower)
        if layer is not None:
            layer["mlp"] = rms_of(f)
        h = h + f
    h_rows = h[jnp.asarray(rows, jnp.int32)]
    head = params[f"{name}_lm_head_weight"]
    V = head.shape[1]
    step = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    out = [np.asarray(_head(h_rows, params[f"{name}_ln_f_scale"],
                            head[:, v0:v0 + step], eps, lower))
           for v0 in range(0, V, step)]
    logits = np.concatenate(out, axis=1) * float(c["lm_head_multiplier"])
    if stats is not None:
        stats["logits"] = float(logits.std())
    return logits, np.stack(states)
