"""The plain reference of the ``nemotron_h`` decoder
(``nemotron_h.NemotronHConfig``): layers that are EACH one part on one
RMSNorm, in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — a full forward over one
whole sequence, the recurrence as a plain ``lax.scan`` over positions
(NOT the chunked form), every expert over every row under a dense mask
(no sort, no grouped product), no kernel, no cache, no state carried in,
no batching.  Written from the equations below and not from
``ssm_decode`` / ``moe_decode``; the serving path (chunked prefill
through pages and slot state, the chunked scan, decode by one step, the
sorted assignments) is tested against it, logits and states.

``u = rms(h; g_i)``, then ``h <- h + part(u)``, a layer one letter of
the pattern; ``h_0 = Emb[id]``; logits ``rms(h; g_f) W_head``:

  M   [z | xBC | dt] = u W_in; xBC <- silu(conv1d(xBC) + b) (depthwise,
      causal, K taps, zeros before the sequence); x (H heads of P), B, C
      (G groups of N, a group serving H / G heads);
      dt <- softplus(dt + dt_bias); A = -exp(A_log);
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
      y <- rms_grouped(y * silu(z); G groups) (the gate first); y W_out
  *   q, k, v = u W_q, u W_k, u W_v (H_q / H_kv / H_kv heads of
      head_dim); NO rotation and no position added anywhere; causal
      softmax(q k^T / sqrt(head_dim)) v, query head n reading K/V head
      n // (H_q / H_kv); o W_o
  E   s = sigmoid(u W_r) over ALL the experts; chosen = the k largest of
      s + b; w = scale * s[chosen] / (sum s[chosen] + 1e-20), the sum
      over ALL the chosen; l = u W_lat_in;
      r = sum over chosen AND held e of w_e relu(l W_up,e)^2 W_down,e;
      r W_lat_out + relu(u W_s,up)^2 W_s,down

``held`` (first, count): the experts the leaves ``moe_experts_*`` hold,
of the router's ``n_routed_experts`` (all, by default; leaves that hold
all of them are cut to the share).  ``vocab`` (first, count): the rows of
the embedding and the columns of the head that are held, a token id
counting from ``first`` (all, by default; tables that hold all are cut).
One function is so the uncut model and one chip's share of it.

Departures from the family's public code: ``dt`` is not clamped (its
``time_step_limit`` is (0, inf)); the family's attention class carries
``rope_theta`` and ``partial_rotary_factor`` and reads neither, and so
does this.

``omit`` computes something else on purpose, for the tests that show the
comparison notices each: "mixer" (the M layers add nothing), "latent"
(the latent projections left out: the experts' mix is added as if it
were zero, since it has the wrong width), "carry" (the matrix state is
zeroed before position ``carry_at``), "position" (every key stands one
position on from its own), "state_bf16" (the matrix state rounded to
bfloat16 every step), "wrong_share" (the held leaves are taken for the
NEXT ``count`` experts), "norm_held" (the weights normalised over the
chosen experts that are held alone).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

OMISSIONS = ("mixer", "latent", "carry", "position", "state_bf16",
             "wrong_share", "norm_held")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def attention(params, us, cfg, u, omit=None):
    """The ``*`` layer's part over one sequence ``u`` [S, d]."""
    S = u.shape[0]
    H, Hkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q = (u @ params[f"{us}_attn_q_weight"]).reshape(S, Hkv, H // Hkv, dh)
    k = (u @ params[f"{us}_attn_k_weight"]).reshape(S, Hkv, dh)
    v = (u @ params[f"{us}_attn_v_weight"]).reshape(S, Hkv, dh)
    if omit == "position":
        k = jnp.pad(k, ((1, 0), (0, 0), (0, 0)))[:S]
    s = jnp.einsum("qhgd,shd->hgqs", q, k) * dh ** -0.5
    live = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), -1)
    o = jnp.einsum("hgqs,shd->qhgd", p, v).reshape(S, H * dh)
    return o @ params[f"{us}_attn_proj_weight"]


def mixer(params, us, cfg, u, omit=None, carry_at=0):
    """(the ``M`` layer's part over one sequence ``u`` [S, d], its matrix
    state [H, P, N] after the last position): the recurrence position by
    position."""
    sp = cfg.ssm
    S = u.shape[0]
    H, P, N, G, K = sp.heads, sp.head_dim, sp.state, sp.groups, sp.conv_kernel
    d_ssm, gn = H * P, G * N
    proj = u @ params[f"{us}_ssm_in_weight"]
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * gn], -1)
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))               # zeros before 0
    w = params[f"{us}_ssm_conv_weight"]
    xbc = jax.nn.silu(sum(w[j] * pad[j:j + S] for j in range(K))
                      + params[f"{us}_ssm_conv_bias"])
    xs = xbc[:, :d_ssm].reshape(S, H, P)
    Bm = jnp.repeat(xbc[:, d_ssm:d_ssm + gn].reshape(S, G, N), H // G, 1)
    Cm = jnp.repeat(xbc[:, d_ssm + gn:].reshape(S, G, N), H // G, 1)
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

    state, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                            (jnp.arange(S), xs, Bm, Cm, dt))
    y = (y + params[f"{us}_ssm_D"][:, None] * xs).reshape(S, d_ssm)
    g = (y * jax.nn.silu(z)).reshape(S, G, d_ssm // G)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + cfg.norm_eps)
    y = g.reshape(S, d_ssm) * params[f"{us}_ssm_norm_scale"]
    return y @ params[f"{us}_ssm_out_weight"], state


def expert_layer(params, us, cfg, u, held=None, omit=None):
    """The ``E`` layer's part over the rows ``u`` [S, d], in its pieces:
    {"routed": the held experts' weighted mix at the latent width [S,
    latent], "out": the whole part (``routed W_lat_out`` + the shared
    expert), "shared": the shared expert alone, "margin": each row's gap
    between the last chosen and the first not chosen of ``s + b``}."""
    E, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    first, count = held or (0, E)
    up = params[f"{us}_moe_experts_up"]
    down = params[f"{us}_moe_experts_down"]
    if up.shape[0] != count:               # leaves that hold every expert
        up, down = up[first:first + count], down[first:first + count]
    if omit == "wrong_share":
        first = first + count
    s = jax.nn.sigmoid(u @ params[f"{us}_moe_router_weight"])   # [S, E]
    pick = s + params[f"{us}_moe_router_bias"]
    ranked = jnp.sort(pick, axis=-1)[:, ::-1]
    chosen = pick >= ranked[:, k - 1:k]
    is_held = (jnp.arange(E) >= first) & (jnp.arange(E) < first + count)
    over = chosen & is_held if omit == "norm_held" else chosen
    w = jnp.where(chosen, s, 0.0)
    if cfg.norm_topk_prob:
        w = w / (jnp.where(over, s, 0.0).sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.routed_scaling_factor
    lat = u @ params[f"{us}_moe_latent_in_weight"]
    r = jnp.zeros_like(lat)
    for e in range(count):
        we = w[:, (first + e) % E]
        r = r + we[:, None] * (_relu2(lat @ up[e]) @ down[e])
    shared = _relu2(u @ params[f"{us}_moe_shared_up_weight"]) \
        @ params[f"{us}_moe_shared_down_weight"]
    out = shared if omit == "latent" \
        else r @ params[f"{us}_moe_latent_out_weight"] + shared
    return {"routed": r, "shared": shared, "out": out,
            "margin": ranked[:, k - 1] - ranked[:, k]}


def forward(params, cfg, tokens, name="nmh", held=None, vocab=None,
            omit=None, carry_at=0, stats=None):
    """(logits [S, V held] float32, the M layers' matrix states [M
    layers, H, P, N] after the last position, each position's smallest
    selection margin over the E layers [S]) for one sequence ``tokens``
    [S].  ``stats`` (a dict) receives the RMS of the residual and of
    each layer's part."""
    if omit is not None and omit not in OMISSIONS:
        raise ValueError(f"omit={omit!r} not in {OMISSIONS}")
    eps = cfg.norm_eps
    rms_of = lambda a: float(jnp.sqrt(jnp.mean(a * a)))    # noqa: E731
    with jax.default_matmul_precision("highest"):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
                  if k.startswith(name + "_")}
        tokens = jnp.asarray(tokens, jnp.int32)
        table, head = params[f"{name}_wte_table"], \
            params[f"{name}_lm_head_weight"]
        v0, vn = vocab or (0, table.shape[0])
        if table.shape[0] != vn:           # tables that hold every row
            table, head = table[v0:v0 + vn], head[:, v0:v0 + vn]
        h = table[tokens]
        states, margin = [], jnp.full(tokens.shape, jnp.inf)
        for i, letter in enumerate(cfg.pattern):
            us = f"{name}_h{i}"
            if letter == "M":
                u = _rms(h, params[f"{us}_ln1_scale"], eps)
                part, state = mixer(params, us, cfg, u, omit, carry_at)
                states.append(state)
                if omit == "mixer":
                    part = jnp.zeros_like(part)
            elif letter == "*":
                u = _rms(h, params[f"{us}_ln1_scale"], eps)
                part = attention(params, us, cfg, u, omit)
            else:
                u = _rms(h, params[f"{us}_ln2_scale"], eps)
                e = expert_layer(params, us, cfg, u, held, omit)
                part, margin = e["out"], jnp.minimum(margin, e["margin"])
            if stats is not None:
                stats.setdefault("layers", []).append(
                    {"kind": letter, "residual": rms_of(h),
                     "part": rms_of(part)})
            h = h + part
        logits = _rms(h, params[f"{name}_ln_f_scale"], eps) @ head
        if stats is not None:
            stats["logits"] = float(logits.std())
        return logits, jnp.stack(states) if states else None, margin
