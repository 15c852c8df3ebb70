"""The plain reference of the state-space hybrid decoder
(``ssm_decode.SSMHybridConfig``, the ``falcon_h1`` family): every layer a
Mamba-2 mixer AND grouped-query attention on one RMSNorm, then a SwiGLU,
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
— a full forward over one whole sequence, the recurrence as a plain
``lax.scan`` over positions (NOT the chunked form), no kernel, no cache,
no state carried in, no batching.  The serving path (chunked prefill
through a paged K/V pool and the slot states, the chunked scan, decode
by one step) is tested against it, logits not tokens.

Per layer with input ``h``, ``u = rms(h; input_layernorm)``:

  h <- h + ssm_out * Mixer(ssm_in * u) + attention_out * Attn(attention_in * u)
  h <- h + MLP(rms(h; pre_ff_layernorm))

  Attn    q, k, v = x W_q, x W_k * key, x W_v (H / H_kv / H_kv heads of
          head_dim, no biases); rotate-half RoPE over the whole head,
          theta rope_theta, angles in float32; causal
          softmax(q k^T / sqrt(head_dim)) v, query head n reading K/V
          head n // (H / H_kv); W_o
  Mixer   [z | xBC | dt] = (x W_in) * mup (the five ssm multipliers over
          the slices z, x, B, C, dt); xBC <- silu(conv1d(xBC) + b)
          (depthwise, causal, K taps, zeros before the sequence); x (H_s
          heads of P), B, C (G groups of N, a group serving H_s / G
          heads); dt <- softplus(dt + dt_bias); A = -exp(A_log);
          S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
          y <- rms_grouped(y * silu(z); G groups) (the gate first); W_out
  MLP     (silu(mlp_gate * x W_gate) * x W_up) W_down * mlp_down
  model   embedding * embedding_multiplier; final rms; untied head;
          logits * lm_head_multiplier

Departures from the family's public implementation: none in the
mathematics.  ``dt`` is not clamped (the family's ``time_step_limit`` is
(0, inf)); the residual adds the attention's output, then the mixer's
(the family's code sums the two first).

``omit`` leaves out one part of the mathematics at a time; it exists for
the tests that show the comparison notices each: "ssm" (the mixer's
branch), "attention" (the attention's), "carry" (the matrix state is
zeroed before position ``carry_at``), "position" (the keys' RoPE positions
shifted by one against the queries': shifting both moves nothing, the
rotation is relative), "state_bf16" (the matrix state rounded to bfloat16
every step).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

OMISSIONS = ("ssm", "attention", "carry", "position", "state_bf16")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta, shift=0):
    """x [S, H, d] at positions shift..shift+S-1, rotate-half over d."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = ((jnp.arange(S, dtype=jnp.float32) + shift)[:, None]
           * inv)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(params, us, cfg, x, omit=None):
    """``Attn(x)`` over one sequence [S, d] (``x`` already times
    ``attention_in``)."""
    S = x.shape[0]
    H, Hkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    shift = 1 if omit == "position" else 0
    q = (x @ params[f"{us}_attn_q_weight"]).reshape(S, H, dh)
    k = (x @ params[f"{us}_attn_k_weight"] * cfg.mup.key).reshape(S, Hkv, dh)
    v = (x @ params[f"{us}_attn_v_weight"]).reshape(S, Hkv, dh)
    q = _rope(q, cfg.rope_theta).reshape(S, Hkv, H // Hkv, dh)
    k = _rope(k, cfg.rope_theta, shift)
    s = jnp.einsum("qhgd,shd->hgqs", q, k) * dh ** -0.5
    live = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
    o = jnp.einsum("hgqs,shd->qhgd", p, v).reshape(S, H * dh)
    return o @ params[f"{us}_attn_proj_weight"], s


def mixer(params, us, cfg, x, omit=None, carry_at=0):
    """``Mixer(x)`` over one sequence [S, d] (``x`` already times
    ``ssm_in``): the recurrence position by position."""
    sp, mup = cfg.ssm, cfg.mup
    S = x.shape[0]
    H, P, N, G, K = sp.heads, sp.head_dim, sp.state, sp.groups, sp.conv_kernel
    gn = G * N
    proj = (x @ params[f"{us}_ssm_in_weight"]) * sp.mup_vector(mup)
    z, xbc, dt = jnp.split(proj, [sp.width, sp.width + sp.conv_width], -1)
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))               # zeros before 0
    w = params[f"{us}_ssm_conv_weight"]
    xbc = jax.nn.silu(sum(w[j] * pad[j:j + S] for j in range(K))
                      + params[f"{us}_ssm_conv_bias"])
    xs = xbc[:, :sp.width].reshape(S, H, P)
    Bm = jnp.repeat(xbc[:, sp.width:sp.width + gn].reshape(S, G, N),
                    H // G, axis=1)                        # [S, H, N]
    Cm = jnp.repeat(xbc[:, sp.width + gn:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + params[f"{us}_ssm_dt_bias"])  # [S, H]
    A = -jnp.exp(params[f"{us}_ssm_A_log"])

    def step(state, row):
        t, xt, bt, ct, dtt = row
        if omit == "carry":
            state = jnp.where(t == carry_at, 0.0, state)
        state = state * jnp.exp(dtt * A)[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        if omit == "state_bf16":
            # (not a pair of casts: the TPU compiler keeps the excess
            # precision of a float32 -> bfloat16 -> float32 round trip)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("hpn,hn->hp", state, ct)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (jnp.arange(S), xs, Bm, Cm, dt))
    y = (y + params[f"{us}_ssm_D"][:, None] * xs).reshape(S, sp.width)
    g = (y * jax.nn.silu(z)).reshape(S, G, sp.width // G)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + cfg.rms_norm_eps)
    y = g.reshape(S, sp.width) * params[f"{us}_ssm_norm_scale"]
    return y @ params[f"{us}_ssm_out_weight"]


def forward(params, cfg, tokens, name="fh1", omit=None, carry_at=0,
            stats=None):
    """Logits [S, V] float32 for one sequence ``tokens`` [S].  ``stats``
    (a dict) receives the RMS of each branch's contribution to the
    residual, of the scores and of the logits, a layer."""
    if omit is not None and omit not in OMISSIONS:
        raise ValueError(f"omit={omit!r} not in {OMISSIONS}")
    mup, eps = cfg.mup, cfg.rms_norm_eps
    rms_of = lambda a: float(jnp.sqrt(jnp.mean(a * a)))    # noqa: E731
    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
                  if k.startswith(name + "_")}
        tokens = jnp.asarray(tokens, jnp.int32)
        h = params[f"{name}_wte_table"][tokens] * mup.embedding
        for i in range(cfg.num_hidden_layers):
            us = f"{name}_h{i}"
            u = _rms(h, params[f"{us}_ln1_scale"], eps)
            a, s = attention(params, us, cfg, u * mup.attention_in, omit)
            a = a * mup.attention_out
            m = mixer(params, us, cfg, u * mup.ssm_in, omit,
                      carry_at) * mup.ssm_out
            if stats is not None:
                live = jnp.tril(jnp.ones(s.shape[-2:], bool))
                stats.setdefault("layers", []).append({
                    "residual": rms_of(h), "attention": rms_of(a),
                    "ssm": rms_of(m),
                    "scores": float(jnp.sqrt(
                        jnp.sum(jnp.where(live, s, 0.0) ** 2)
                        / (live.sum() * s.shape[0] * s.shape[1])))})
            if omit != "attention":
                h = h + a
            if omit != "ssm":
                h = h + m
            x = _rms(h, params[f"{us}_ln2_scale"], eps)
            gate = jax.nn.silu(x @ params[f"{us}_ffn_gate_weight"]
                               * mup.mlp_gate)
            f = (gate * (x @ params[f"{us}_ffn_up_weight"])) \
                @ params[f"{us}_ffn_down_weight"] * mup.mlp_down
            if stats is not None:
                stats["layers"][-1]["mlp"] = rms_of(f)
            h = h + f
        h = _rms(h, params[f"{name}_ln_f_scale"], eps)
        logits = h @ params[f"{name}_lm_head_weight"] * mup.lm_head
        if stats is not None:
            stats["logits"] = float(logits.std())
        return logits
