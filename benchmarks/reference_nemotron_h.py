"""Nemotron-H's layer stack in plain float32 ``jax.numpy``, precision
``highest``: the benchmark's own copy of the reference (the equations of
``hetu_tpu/models/reference_nemotron_h.py``, written again here and not
imported: the yardstick must not move with the program), laid out so
that a 3,584-token sequence fits on the chip beside 9.3 GB of served
weights.  It decides ``correct``.

The equations are the family's public ones (``nemotron_h``): no cache, no
state carried in, no batching, one sequence at a time, every expert over
every row under a dense mask (no sort, no grouped product).  A layer is
ONE part on ONE norm, ``h <- h + part(RMSNorm(h; g_i, 1e-5))``, the
letter of ``hybrid_override_pattern`` saying which; ``h_0 = Emb[id]``
(no multiplier, no position added); logits ``RMSNorm(h; g_f) W_head``:

  M   [z | xBC | dt] = u W_in (8,192 | 10,240 | 128 columns, no bias);
      xBC <- silu(conv1d(xBC) + b), depthwise, causal, 4 taps, zeros
      before the sequence; x (128 heads of 64), B, C (8 groups of 128, a
      group serving 16 heads); dt <- softplus(dt + dt_bias);
      A = -exp(A_log) a head;
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   ([64, 128] a head),
      y_t = S_t C_t + D x_t, as a ``lax.scan`` over POSITIONS (not the
      chunked form); y <- RMSNorm over 8 groups of (y * silu(z)) (the
      gate first); y W_out
  *   q = u W_q (32 heads of 128), k = u W_k, v = u W_v (2 heads); NO
      rotation and no position anywhere; causal
      softmax(q k^T / sqrt(128)) v, query head n reading K/V head
      n // 16; o W_o
  E   s = sigmoid(u W_r) over ALL 512; chosen = the 22 largest of s + b;
      w = 5 s[chosen] / (sum s[chosen] + 1e-20), the sum over all 22
      whether held or not; l = u W_lat_in (4,096 -> 1,024);
      r = sum over chosen AND held e of w_e relu(l W_up,e)^2 W_down,e
      (1,024 -> 2,688 -> 1,024); r W_lat_out + relu(u W_s,up)^2 W_s,down

``held`` (first, count) says which of the router's experts the leaves
``moe_experts_*`` hold (this chip's share of an expert-parallel
deployment; all, by default).  The vocabulary slice needs no argument:
the tables the runner hands over ARE the rows and columns held, and a
token id counts from the slice's first row.  One function is so the
uncut model and the chip's share.

What differs from the program's copy is only how the work is cut: every
part of every layer is one jitted call whose weights are upcast inside
it, the held experts are taken one at a time by a ``lax.scan`` inside
that call, the query rows of attention are taken ``ROW_BLOCK`` at a
time, and the head runs over ``VOCAB_BLOCK`` columns at a time for the
answer's rows only.  ``_mm``, ``_rms`` and ``_head`` are
``reference_glm47flash``'s own (the same equations; the benchmark's code,
not the program's).  Departures from the family's public code: ``dt`` is
not clamped (its ``time_step_limit`` is (0, inf)); the family's attention
class carries ``rope_theta`` and ``partial_rotary_factor`` and reads
neither, and neither does this.

Besides the logits it returns every M layer's matrix state after the
last real position, which the runner sets against the state the engine
left in the slot, and every position's smallest selection margin over
the E layers (the 22nd against the 23rd of ``s + b``).

``control`` computes something else ON PURPOSE, each of which the
comparison has to call not correct (``probe_nemotron_h_check.py``):
"float8" rounds the operands of every weight product to float8 (e4m3),
the nearest precision below the bfloat16 the configuration states;
"state_bf16" rounds the matrix state to bfloat16 after every step;
"carry" zeroes the matrix state at position ``carry_at`` (a chunk
boundary); "position" lets every key stand one position on from its own;
"mixer" leaves the M layers out of the residual; "latent" leaves the
latent projections out (the experts' mix has the wrong width without
them and adds nothing); "wrong_share" takes the held leaves for experts
``[first + count, first + 2 count)``; "norm_held" normalises the weights
over the chosen experts that are HELD alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_glm47flash import _head, _mm, _rms

ROW_BLOCK = 256
VOCAB_BLOCK = 8192
CONTROLS = ("float8", "state_bf16", "carry", "position", "mixer", "latent",
            "wrong_share", "norm_held")
# the controls each part's jitted call is handed (the others reach it as
# None, so that it is traced once for all of them)
_OF_PART = {"mixer": ("float8", "state_bf16", "carry"),
            "attention": ("float8", "position"),
            "experts": ("float8", "latent", "wrong_share", "norm_held")}


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _attention(h, w, sizes, control):
    """The ``*`` layer's part, rows in blocks of ``ROW_BLOCK``."""
    H, Hkv, dh, eps = sizes
    mm = _mm(control == "float8")
    S = h.shape[0]
    u = _rms(h, w["ln"].astype(jnp.float32), eps)
    q = mm(u, w["q"]).reshape(S, Hkv, H // Hkv, dh)  # head n = (n // g, n % g)
    k = mm(u, w["k"]).reshape(S, Hkv, dh)
    v = mm(u, w["v"]).reshape(S, Hkv, dh)
    if control == "position":
        k = jnp.pad(k, ((1, 0), (0, 0), (0, 0)))[:S]
    block = min(ROW_BLOCK, S)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(q, r0, block, 0)
        s = jnp.einsum("qhgd,shd->hgqs", qb, k,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32) * dh ** -0.5
        live = jnp.arange(S)[None, :] <= (r0 + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf), -1)
        return jnp.einsum("hgqs,shd->qhgd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    o = jax.lax.map(rows, jnp.arange(0, S, block))
    return mm(o.reshape(S, H * dh), w["proj"])


@functools.partial(jax.jit, static_argnames=("sizes", "control", "carry_at"))
def _mixer(h, w, n, sizes, control, carry_at):
    """(the ``M`` layer's part, the matrix state after position
    ``n - 1``): the recurrence position by position; positions from
    ``n`` on (the padding) have ``dt`` 0 and move nothing."""
    Hs, P, N, G, K, eps = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    S = h.shape[0]
    d_ssm, gn = Hs * P, G * N
    u = _rms(h, f32(w["ln"]), eps)
    proj = mm(u, w["in"])
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
    return mm(y, w["out"]), state


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _experts(h, w, sizes, control):
    """(the ``E`` layer's part, each row's selection margin): the held
    experts one at a time over every row, a dense mask of weights."""
    k, scale, norm, first, count, eps = sizes
    mm = _mm(control == "float8")
    u = _rms(h, w["ln"].astype(jnp.float32), eps)
    s = jax.nn.sigmoid(mm(u, w["router"]))                 # [S, E]
    E = s.shape[1]
    pick = s + w["bias"].astype(jnp.float32)
    ranked = jnp.sort(pick, axis=-1)[:, ::-1]
    chosen = pick >= ranked[:, k - 1:k]
    if control == "wrong_share":
        first = first + count
    ids = (first + jnp.arange(count)) % E
    over = chosen
    if control == "norm_held":
        over = chosen & jnp.zeros((E,), bool).at[ids].set(True)
    wts = jnp.where(chosen, s, 0.0)
    if norm:
        wts = wts / (jnp.where(over, s, 0.0).sum(-1, keepdims=True) + 1e-20)
    wts = wts * scale
    lat = mm(u, w["lat_in"])

    def one(r, e):
        up, down, we = e
        return r + we[:, None] * mm(_relu2(mm(lat, up)), down), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                        (w["up"], w["down"], wts[:, ids].T))
    shared = mm(_relu2(mm(u, w["shared_up"])), w["shared_down"])
    out = shared if control == "latent" else mm(r, w["lat_out"]) + shared
    return out, ranked[:, k - 1] - ranked[:, k]


def forward(params, config, tokens, rows, n=None, name="nmh", held=None,
            control=None, carry_at=0, stats=None):
    """(logits [len(rows), V held] as numpy float32, states [M layers,
    Hs, P, N] as numpy float32, margin [S] as numpy) for the sequence
    ``tokens`` [S] (``S`` a multiple of ``ROW_BLOCK`` or below it) of
    which the first ``n`` are real (all, by default): the next-token
    logits after each position in ``rows``, every M layer's matrix state
    after position ``n - 1`` and every position's smallest selection
    margin over the E layers.  ``config`` holds the source's keys,
    ``n_routed_experts`` the ROUTER's width.  ``stats`` (a dict)
    receives, a layer, its letter, the RMS of the residual and of the
    layer's part, and the logits' standard deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    eps = float(c["layer_norm_epsilon"])
    lower = control == "float8"
    first, count = held or (0, c["n_routed_experts"])
    attn_sizes = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"], eps)
    ssm_sizes = (c["mamba_num_heads"], c["mamba_head_dim"],
                 c["ssm_state_size"], c["n_groups"], c["conv_kernel"], eps)
    moe_sizes = (c["num_experts_per_tok"], float(c["routed_scaling_factor"]),
                 bool(c["norm_topk_prob"]), int(first), int(count), eps)
    of = {part: control if control in names else None
          for part, names in _OF_PART.items()}
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    n = jnp.int32(S if n is None else n)
    real = np.arange(S) < int(n)
    rms_of = lambda a: float(np.sqrt(                      # noqa: E731
        np.mean(np.square(np.asarray(a)[real]))))
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    states, margin = [], np.full(S, np.inf, np.float32)
    for i, letter in enumerate(c["hybrid_override_pattern"]):
        us = f"{name}_h{i}"
        if letter == "M":
            part, state = _mixer(h, {
                "ln": params[f"{us}_ln1_scale"],
                "in": params[f"{us}_ssm_in_weight"],
                "taps": params[f"{us}_ssm_conv_weight"],
                "conv_bias": params[f"{us}_ssm_conv_bias"],
                "dt_bias": params[f"{us}_ssm_dt_bias"],
                "A_log": params[f"{us}_ssm_A_log"],
                "D": params[f"{us}_ssm_D"],
                "norm": params[f"{us}_ssm_norm_scale"],
                "out": params[f"{us}_ssm_out_weight"]}, n, ssm_sizes,
                of["mixer"], int(carry_at))
            states.append(np.asarray(state))
            if control == "mixer":
                part = jnp.zeros_like(part)
        elif letter == "*":
            part = _attention(h, {
                "ln": params[f"{us}_ln1_scale"],
                "q": params[f"{us}_attn_q_weight"],
                "k": params[f"{us}_attn_k_weight"],
                "v": params[f"{us}_attn_v_weight"],
                "proj": params[f"{us}_attn_proj_weight"]}, attn_sizes,
                of["attention"])
        else:
            part, gap = _experts(h, {
                "ln": params[f"{us}_ln2_scale"],
                "router": params[f"{us}_moe_router_weight"],
                "bias": params[f"{us}_moe_router_bias"],
                "lat_in": params[f"{us}_moe_latent_in_weight"],
                "lat_out": params[f"{us}_moe_latent_out_weight"],
                "up": params[f"{us}_moe_experts_up"],
                "down": params[f"{us}_moe_experts_down"],
                "shared_up": params[f"{us}_moe_shared_up_weight"],
                "shared_down": params[f"{us}_moe_shared_down_weight"]},
                moe_sizes, of["experts"])
            margin = np.minimum(margin, np.asarray(gap))
        if stats is not None:
            stats.setdefault("layers", []).append(
                {"kind": letter, "residual": rms_of(h),
                 "part": rms_of(part)})
        h = h + part
    h_rows = h[jnp.asarray(rows, jnp.int32)]
    head = params[f"{name}_lm_head_weight"]
    V = head.shape[1]
    step = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    out = [np.asarray(_head(h_rows, params[f"{name}_ln_f_scale"],
                            head[:, v0:v0 + step], eps, lower))
           for v0 in range(0, V, step)]
    logits = np.concatenate(out, axis=1)
    if stats is not None:
        stats["logits"] = float(logits.std())
    return logits, np.stack(states), margin
