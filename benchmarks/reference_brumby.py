"""Brumby's block stack in plain float32 ``jax.numpy``, precision
``highest``, in the ATTENTION form: the benchmark's own copy of the
reference (the equations of ``hetu_tpu/models/reference_retention.py``,
written again here and not imported: the yardstick must not move with
the program), laid out so that a 16,640-token sequence fits on the chip
beside 7.1 GB of served weights.  It decides ``correct``.

The equations are the family's public ones (power retention,
arXiv:2507.04239, on the Qwen3 block the model was built from): no
state, no ``phi``, no chunked recurrence, no cache, no batching, one
sequence at a time.  With ``u`` the RMSNorm of the residual ``h`` (eps
1e-6, no biases):

  q, k, v  u W_q (40 heads of 128), u W_k, u W_v (8 heads); q and k
           through the per-head RMSNorm over 128 (learned scales), then
           rotate-half RoPE over all 128 columns, theta 1e6, angles in
           float32
  gate     lg_t = log sigmoid(u_t W_g + b_g), one a K/V head a token
  weights  a_tj = (q_t . k_j)^2 exp(sum_{l = j+1 .. t} lg_l), j <= t,
           query head n reading K/V head n // 5
  y_t      sum_j a_tj v_j / sum_j a_tj; concat over heads; W_o
  h <- h + y;  h <- h + (silu(x W_gate) * x W_up) W_down, x = RMSNorm(h)
  model    embedding; final RMSNorm; untied head

What differs from the program's copy is only how the work is cut, so
that ONE compiled function serves every sequence length: the rows are
taken ``ROW_BLOCK`` at a time (projections, MLP), a block of queries
meets the blocks of keys at or before it one pair at a time and the
numerator and the denominator are summed over the pairs, and the head
runs over ``VOCAB_BLOCK`` columns at a time for the answer's rows only.
``_mm``, ``_rms`` and ``_head`` are ``reference_glm47flash``'s own (the
same equations; the benchmark's code, not the program's).  Departures
from the published description: any scale on ``q . k`` is left out (it
cancels between numerator and denominator); no epsilon in the
denominator (the ``j = t`` term is a square); the package's inference
switch from K/V to the state form at a sequence length is not part of
the function.

Besides the logits it answers ``probes`` [M, 8, 128]: extra queries set
after the last real position ``n - 1`` (no gate between it and them),
for which it returns the numerators ``sum_j a_j v_j`` [layers, M, 8,
128] and denominators ``sum_j a_j`` [layers, M, 8] UNDIVIDED.  Through
``phi`` they are what the state the engine left in a slot reads
(``phi(r)^T S`` and ``phi(r) . z``), and the runner sets the two side by
side (``runners/serve_retention.py``: the state's own check).

``control`` computes something else ON PURPOSE, each of which the
comparison has to call not correct (``probe_brumby_check.py``):
"float8" rounds the operands of every weight product to float8 (e4m3),
the nearest precision below the bfloat16 the configuration states;
"gate" leaves the gates out (every ``lg`` 0); "position" rotates the
keys one position on from the queries.  (The state kept in bfloat16 is a
control on the PROGRAM's side: the reference has no state to round.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference_glm47flash import _head, _mm, _rms

ROW_BLOCK = 1024
VOCAB_BLOCK = 18992          # 151,936 / 8
CONTROLS = ("float8", "gate", "position")
HIGHEST = jax.lax.Precision.HIGHEST


def _rope(x, posns, theta):
    """x [S, H, d] at positions ``posns`` [S], rotate-half over d."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (posns.astype(jnp.float32)[:, None] * inv)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "control"))
def _front(h, r0, w, sizes, control):
    """(q [B, g, m, d], k, v [B, g, d], lg [B, g]) of the rows ``h`` [B,
    hidden] at positions ``r0 ..``."""
    n, g, d, eps, theta = sizes
    mm = _mm(control == "float8")
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    B = h.shape[0]
    posns = r0 + jnp.arange(B)
    u = _rms(h, f32(w["ln1"]), eps)
    q = _rms(mm(u, w["q"]).reshape(B, n, d), f32(w["q_norm"]), eps)
    k = _rms(mm(u, w["k"]).reshape(B, g, d), f32(w["k_norm"]), eps)
    q = _rope(q, posns, theta).reshape(B, g, n // g, d)
    k = _rope(k, posns + (1 if control == "position" else 0), theta)
    v = mm(u, w["v"]).reshape(B, g, d)
    lg = jax.nn.log_sigmoid(mm(u, w["gate"]) + f32(w["gate_bias"]))
    if control == "gate":
        lg = jnp.zeros_like(lg)
    return q, k, v, lg


@jax.jit
def _pair(q, cum_q, t0, k, v, cum_k, j0):
    """What the keys ``k``, ``v`` [C, g, d] at positions ``j0 ..`` add to
    the queries ``q`` [B, g, m, d] at positions ``t0 ..``: (numerator
    [B, g, m, d], denominator [B, g, m]); ``cum_q`` [B, g] / ``cum_k``
    [C, g] the gates' running sums at each.  A key after its query adds
    nothing (masked BEFORE the exponential: the sum of the gates between
    them would be positive)."""
    B, C = q.shape[0], k.shape[0]
    live = (j0 + jnp.arange(C))[None, :] <= (t0 + jnp.arange(B))[:, None]
    decay = jnp.exp(jnp.where(
        live[None], cum_q.T[:, :, None] - cum_k.T[:, None, :], -jnp.inf))
    s = jnp.einsum("tgmd,jgd->gmtj", q, k, precision=HIGHEST,
                   preferred_element_type=jnp.float32)
    a = s * s * decay[:, None]                             # [g, m, B, C]
    num = jnp.einsum("gmtj,jgd->tgmd", a, v, precision=HIGHEST)
    return num, a.sum(-1).transpose(2, 0, 1)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _back(h, y, w, eps, lower):
    """(h + y W_o + MLP, the RMS of y W_o, of the MLP) for a block of
    rows."""
    mm = _mm(lower)
    o = mm(y, w["proj"])
    h = h + o
    x = _rms(h, w["ln2"].astype(jnp.float32), eps)
    f = mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])
    return h + f, jnp.mean(o * o), jnp.mean(f * f)


def forward(params, config, tokens, rows, n=None, name="bru", control=None,
            probes=None, stats=None):
    """(logits [len(rows), V] numpy float32, (numerators [layers, M, g,
    d], denominators [layers, M, g]) of ``probes`` or None) for the
    sequence ``tokens`` [S] (``S`` a multiple of ``ROW_BLOCK`` or below
    it) of which the first ``n`` are real (all, by default): the
    next-token logits after each position in ``rows``, and what queries
    ``probes`` set after position ``n - 1`` read.  ``config`` holds the
    source's keys.  ``stats`` (a dict) receives, a layer, the RMS of the
    residual and of each branch's contribution, and the logits' standard
    deviation."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"control={control!r} not in {CONTROLS}")
    c = config
    eps = float(c["rms_norm_eps"])
    lower = control == "float8"
    g, d = c["num_key_value_heads"], c["head_dim"]
    sizes = (c["num_attention_heads"], g, d, eps, float(c["rope_theta"]))
    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    n = S if n is None else int(n)
    block = min(ROW_BLOCK, S)
    starts = range(0, S, block)
    real = np.arange(S) < n
    rms_of = lambda a: float(np.sqrt(                      # noqa: E731
        np.mean(np.square(np.asarray(a)[real]))))
    h = params[f"{name}_wte_table"][tokens].astype(jnp.float32)
    hs = [h[r0:r0 + block] for r0 in starts]
    read = []
    for i in range(c["num_hidden_layers"]):
        us = f"{name}_h{i}"
        w = {"ln1": params[f"{us}_ln1_scale"],
             "q": params[f"{us}_attn_q_weight"],
             "k": params[f"{us}_attn_k_weight"],
             "v": params[f"{us}_attn_v_weight"],
             "q_norm": params[f"{us}_attn_q_norm_scale"],
             "k_norm": params[f"{us}_attn_k_norm_scale"],
             "gate": params[f"{us}_ret_gate_weight"],
             "gate_bias": params[f"{us}_ret_gate_bias"]}
        front = [_front(hb, r0, w, sizes, control)
                 for hb, r0 in zip(hs, starts)]
        cum = jnp.cumsum(jnp.concatenate([f[3] for f in front]), axis=0)
        cums = [cum[r0:r0 + block] for r0 in starts]
        ys = []
        for b, (q, _, _, _) in enumerate(front):
            num = den = 0.0
            for cb in range(b + 1):
                pn, pd = _pair(q, cums[b], starts[b], front[cb][1],
                               front[cb][2], cums[cb], starts[cb])
                num, den = num + pn, den + pd
            ys.append((num / den[..., None]).reshape(q.shape[0], -1))
        if probes is not None:
            # queries after the last real position, under its gates' sum
            # (positions n .. are padding: a key there is not in sight)
            r = jnp.asarray(probes, jnp.float32)[:, :, None, :]
            at = jnp.broadcast_to(cum[n - 1], (r.shape[0], g))
            num = den = 0.0
            for cb, j0 in enumerate(starts):
                if j0 < n:
                    pn, pd = _pair(r, at, n - 1 - jnp.arange(r.shape[0]),
                                   front[cb][1], front[cb][2], cums[cb], j0)
                    num, den = num + pn, den + pd
            read.append((np.asarray(num[:, :, 0]), np.asarray(den[:, :, 0])))
        wb = {"proj": params[f"{us}_attn_proj_weight"],
              "ln2": params[f"{us}_ln2_scale"],
              "gate": params[f"{us}_ffn_gate_weight"],
              "up": params[f"{us}_ffn_up_weight"],
              "down": params[f"{us}_ffn_down_weight"]}
        out = [_back(hb, y, wb, eps, lower) for hb, y in zip(hs, ys)]
        if stats is not None:
            stats.setdefault("layers", []).append({
                "residual": rms_of(jnp.concatenate(hs)),
                "retention": float(np.sqrt(np.mean([o[1] for o in out]))),
                "mlp": float(np.sqrt(np.mean([o[2] for o in out])))})
        hs = [o[0] for o in out]
    h_rows = jnp.concatenate(hs)[jnp.asarray(rows, jnp.int32)]
    head = params[f"{name}_lm_head_weight"]
    V = head.shape[1]
    step = VOCAB_BLOCK if V % VOCAB_BLOCK == 0 else V
    logits = np.concatenate(
        [np.asarray(_head(h_rows, params[f"{name}_ln_f_scale"],
                          head[:, v0:v0 + step], eps, lower))
         for v0 in range(0, V, step)], axis=1)
    if stats is not None:
        stats["logits"] = float(logits.std())
    if probes is None:
        return logits, None
    return logits, (np.stack([r[0] for r in read]),
                    np.stack([r[1] for r in read]))
