"""Kimi Delta Attention (KDA, arXiv:2510.26692) for the mixed ragged wave:
a gated DELTA rule with a decay a channel, a matrix state a head a slot
and a short convolution in front of q, k and v (the ``Ling-3.0-flash``
family's linear-attention layers, five to every latent-attention layer).

The layer, with ``u`` the RMSNorm of the residual, ``H`` heads of ``D``
columns (key and value alike), a head at a time:

  front   [q~ | k~ | v~] = u W_qkv (no bias; ONE leaf, the three
          projections side by side as ``ssm_decode``'s ``W_in`` holds
          its slices), each column through a depthwise causal
          convolution of ``K`` taps (no bias:
          ``gpt_decode._causal_conv``, the short convolution the
          ``lfm2_moe`` block runs) whose last ``K - 1`` inputs a slot
          carries, then SiLU; q and k L2-normalised over the head's
          columns (``x rsqrt(sum x^2 + 1e-6)``), q times ``D^-1/2``.
          Nothing is rotated.
  decay   g = lower_bound * sigmoid(exp(A_log_h) (u W_f + dt_bias)),
          float32, a CHANNEL (``lower_bound`` -5: the safe gate);
          alpha = exp(g) in (e^-5, 1)^D.  beta = sigmoid(u W_beta), one a
          head.
  state   S' = Diag(alpha_t) S_{t-1}                       [D, D] a head
          S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
          o_t = S_t^T q_t
  out     y = RMSNorm_D(o_t) * scale_h (a scale a head's column) times
          sigmoid(u W_g)_h (one gate a head); concat(y) W_o

That is the SAFE gate's layer, ``KDASpec``'s defaults.  The layer as
published (arXiv:2510.26692; the ``solar_open2`` family's, three to every
grouped-query layer) is the same recurrence under other values of the
spec, no other code path but where said:

  decay   "softplus": g = -exp(A_log_h) softplus(u W_f + dt_bias), which
          has NO lower bound (one step at the family's constants reaches
          -16 and below), so the chunked form takes its EXACT pairing
          (below); ``rank`` r > 0: ``W_f`` and ``W_g`` are low-rank, ``d
          x r`` then ``r x H D``, the gate's second with a bias
  beta    ``beta_scale`` 2: beta in (0, 2), so that ``I - beta k k^T``
          has the eigenvalue ``1 - beta`` down to -1 (arXiv:2411.12537)
          and the state's part along ``k`` changes SIGN; the step, the
          solve and the update are the same expressions
  out     ``gate_by`` "channel": sigmoid(u W_g + b_g) one a COLUMN

What a sequence carries from one q-block to its next, a slot a layer:
the conv's last ``K - 1`` inputs ``[K - 1, 3 H D]`` in the pool's dtype
(the three tails side by side) and ``S`` ``[H, D, D]`` in FLOAT32 (a
state that is READ to correct it: an error of its bits comes back in
every later step).  Both live in the ``PagedKVManager`` that admits the
slots, beside the latent pool of the block's attention layers
(``KDASpec.state_shapes``), zeroed when a slot is claimed and handed
through the donated step.

One program a bucket serves every kind of row, as ``ssm_decode`` and
``retention_decode`` do.  A slot with ONE live row takes one step of the
recurrence in float32, the whole batch at once (``kda_step``).  A slot
with a wider q-block takes the CHUNKED form (``kda_chunked``) over chunks
of ``CHUNK`` rows in sub-blocks of ``SUB``: with ``G``
the running sum of ``g`` inside a chunk,

    A = strict_lower(beta_i (k_i exp(G_i - G_j)) . k_j)
    [W | U] = (I + A)^-1 [beta k exp(G) | beta v]   (forward substitution)
    o = (q exp(G)) S + lower((q_i exp(G_i - G_j)) . k_j) (U - W S)
    S <- Diag(exp(G_end)) S + (k exp(G_end - G))^T (U - W S)

ONE state update a chunk.  ``exp(G_i - G_j)`` is never formed as ``exp(G_i)
exp(-G_j)`` over a chunk (e^320 at the bound): row ``i`` takes its decay
since ITS sub-block began (at most 1), column ``j`` its decay up to that
point (at most 1 from an earlier sub-block, at most ``e^(5 sub)`` = e^80
inside the row's own: that is what the bound is for), masked before the
exponential elsewhere.  A wave's few wide slots are taken ``WIDE_LANES``
at a time.  A dead row and a dead slot have ``g`` 0, ``beta`` 0 and ``k``
0: decay 1, correction 0, the state stays where it was, bit for bit.  The
With a decay that has no bound (``KDASpec.unbounded``) NO reference
inside a row's own sub-block is safe (sixteen rows of -17.5 are e^280), and
the pairs are formed level by level instead (``_levels``): at the level of
half-blocks of ``s`` rows (1, 2, 4, .. up to half the chunk), a row ``i`` in
the SECOND half of its block of ``2 s`` and a column ``j`` in the FIRST
take the last row ``m`` of that first half as their reference, ``exp(G_i -
G_m) exp(G_m - G_j)``: both exponents are sums of ``g`` over the rows
between, so BOTH are at most 0 whatever ``g`` is, every pair ``j < i``
meets at exactly one level, and a level is one product on the matrix unit
as the sub-blocks' were (six levels a chunk of 64 in place of four
sub-block rows; a factor that underflows belongs to a pair under e^-87).
The
matrix products take their operands in the activations' dtype and
accumulate in float32, but for the two that READ the carried state (``W
S`` and ``(q e^G) S``: float32 operands at precision highest, as the
one-row step reads it); the solve is float32; the state is read, decayed,
corrected and stored in float32.

What runs the chunked form follows the program's shape alone
(``takes_kernel``): where the head is a whole number of lane tiles (the
published model's 128) and the q-block whole chunks, the Pallas kernel
``kernels/kda_scan.kda_chunk_scan`` (ISSUE 59) takes the lanes' rows as
gathered and reads and rewrites the lanes' states where they lie in the
manager's array, the same mathematics at the same precisions with the
decays, both score matrices and the solve in VMEM; everywhere else
(narrow heads, the CPU's small models, a q-block of a few rows)
``kda_chunked`` as XLA's own operations round slices of the state, which
is also the form the kernel is held to (``tests/test_kda_kernel.py``).

Scopes: ``kda_qkvg`` (the norm, the projections, decay and beta),
``kda_conv`` (the three tails' mix and write, SiLU, the L2
normalisation), ``kda_scan`` (step and chunked forms), ``state_write``
(the shared name: the matrix state's store), ``kda_out`` (norm, gate,
``W_o``).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


# the chunked form: ``CHUNK`` rows a state update, in sub-blocks of
# ``SUB`` rows (``-lower_bound * SUB`` has to stay inside float32's
# exponent: ``KDASpec.fits``)
CHUNK = 64
SUB = 16
# the decays and the output gate's granularities ``KDASpec`` names
DECAYS = ("safe", "softplus")
GATES_BY = ("head", "channel")


class KDASpec(NamedTuple):
    """The mixer's sizes: ``heads`` states of ``head_dim`` x ``head_dim``
    a layer a slot, ``conv_kernel`` taps (``short_conv_kernel_size``),
    ``lower_bound`` the safe gate's (``kda_lower_bound``),
    ``state_dtype`` the dtype ``S`` is KEPT in ("float32"; "bfloat16" is
    the control the comparison has to refuse).  ``decay`` "safe" (the
    bounded gate above) | "softplus" (the published one, unbounded:
    ``lower_bound`` is then not read); ``rank`` the low rank of the decay's
    and the output gate's projections (0: full rank, no gate bias);
    ``gate_by`` "head" | "channel"; ``beta_scale`` 1 or 2 (beta in (0,
    2): negative eigenvalues)."""

    heads: int
    head_dim: int
    conv_kernel: int = 4
    lower_bound: float = -5.0
    state_dtype: str = "float32"
    decay: str = "safe"
    rank: int = 0
    gate_by: str = "head"
    beta_scale: float = 1.0

    @property
    def unbounded(self):
        """Whether ``g`` has no lower bound: the chunked form then pairs
        its rows level by level (every exponent at most 0)."""
        return self.decay != "safe"

    @property
    def width(self):
        """``H D``: the columns of q, of k, of v and of the output."""
        return self.heads * self.head_dim

    def fits(self):
        """Whether the mixer runs these values: sizes, a decay of
        ``DECAYS`` (the safe gate's bound keeping the chunked form's
        sub-block exponents inside float32; the unbounded one needs no
        bound), a gate of ``GATES_BY``, beta's scale in (0, 2]."""
        return self.heads >= 1 and self.head_dim >= 1 \
            and self.conv_kernel >= 2 and self.rank >= 0 \
            and self.decay in DECAYS and self.gate_by in GATES_BY \
            and 0 < self.beta_scale <= 2 \
            and (self.unbounded or 0 < -self.lower_bound * SUB <= 85.0)

    def state_shapes(self, layers):
        """The manager's set of slot states for ``layers`` such layers:
        every layer's conv tails ``[1, K - 1, 3 H D]`` in the pool's
        dtype first, then every layer's ``S`` ``[1, H, D, D]``; an array
        a layer of each (``SSMSpec.state_shapes`` says why)."""
        return (((1, self.conv_kernel - 1, 3 * self.width), None),
                ) * layers \
            + (((1, self.heads, self.head_dim, self.head_dim),
                jnp.dtype(self.state_dtype)),) * layers


def l2norm(x, eps=1e-6):
    """``x rsqrt(sum x^2 + eps)`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt((x32 * x32).sum(-1, keepdims=True) + eps)
            ).astype(x.dtype)


def kda_step(q, k, v, g, beta, S):
    """One step of the recurrence for every slot: ``q`` / ``k`` / ``v``
    [B, H, D], ``g`` [B, H, D] float32 (0 with ``beta`` 0 and ``k`` 0:
    the slot does not move), ``beta`` [B, H] float32, ``S`` [B, H, D, D]
    float32 (key column first).  Returns (o [B, H, D] float32, S)."""
    f32 = jnp.float32
    S = S * jnp.exp(g)[..., None]
    r = v.astype(f32) - jnp.einsum("bhk,bhkv->bhv", k.astype(f32), S,
                                   preferred_element_type=f32)
    S = S + (beta[..., None] * k.astype(f32))[..., None] * r[:, :, None, :]
    # the read-out reads the state as WRITTEN (``retention_step``: without
    # the barrier the compiler fuses a second copy of the update into it)
    S = jax.lax.optimization_barrier(S)
    return jnp.einsum("bhk,bhkv->bhv", q.astype(f32), S,
                      preferred_element_type=f32), S


def _levels(c):
    """The level-by-level pairing of a chunk of ``c`` rows, as 0/1
    matrices over (row, row): for the level of half-blocks of ``s`` =
    ``2^l`` rows, ``rows[l, i, t]`` marks the rows ``t`` whose ``g`` sums
    to ``G_i - G_m`` for a row ``i`` in the second half of its block of
    ``2 s`` (``m`` the last row of the first half), ``cols[l, j, t]``
    those that sum to ``G_m - G_j`` for a column ``j`` in the first half,
    and ``pairs[l, i, j]`` the pairs the level owns.  Every ``j < i`` is
    in exactly one level's ``pairs``."""
    r, t = np.arange(c)[:, None], np.arange(c)[None, :]
    rows, cols, pairs = [], [], []
    s = 1
    while s < c:
        second = (r // s) % 2 == 1
        rows.append(second & (t >= r // s * s) & (t <= r))
        cols.append(~second & (t > r) & (t < (r // s + 1) * s))
        pairs.append(second & ((t // s) % 2 == 0)
                     & (r // (2 * s) == t // (2 * s)))
        s *= 2
    return np.stack(rows), np.stack(cols), np.stack(pairs)


def _scores_by_level(qz, kz, gz, bz, cd):
    """(A, P) [.., c, c] float32 of one chunk, ``A`` strictly lower and
    times its row's beta ``bz`` [.., c], ``P`` lower with its diagonal,
    from ``qz`` / ``kz`` / ``gz`` [.., c, D] float32 by ``_levels``'
    pairing: every exponent a sum of ``g``, at most 0; the operands
    rounded to ``cd`` as the sub-blocks' are."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    c = gz.shape[-2]
    rows, cols, pairs = _levels(c)
    er = jnp.exp(jnp.einsum("lrt,...td->...lrd", rows.astype(np.float32),
                            gz, precision=hi))              # <= 1
    ec = jnp.exp(jnp.einsum("lrt,...td->...lrd", cols.astype(np.float32),
                            gz, precision=hi))              # <= 1
    kk = (kz[..., None, :, :] * ec).astype(cd)
    A, P = (jnp.where(pairs, jnp.einsum(
        "...lid,...ljd->...lij", (x[..., None, :, :] * er).astype(cd), kk,
        preferred_element_type=f32), 0.0).sum(-3) for x in (kz, qz))
    # a row's own column decays by nothing
    return A * bz[..., None], P + jnp.where(
        np.eye(c, dtype=bool), jnp.einsum(
            "...id,...jd->...ij", qz.astype(cd), kz.astype(cd),
            preferred_element_type=f32), 0.0)


def _scores_by_sub_block(qz, kz, G, bz, cd):
    """(A, P) as ``_scores_by_level`` gives them, from the running sums
    ``G`` [n, B, H, c, D] of a BOUNDED ``g``: row ``i`` by its decay since
    its sub-block of ``SUB`` began, column ``j`` by its decay up to that
    point (at most ``e^(-bound SUB)`` inside the row's own sub-block)."""
    f32 = jnp.float32
    lead, (c, D) = G.shape[:-2], G.shape[-2:]
    m = c // SUB
    Gb = G.reshape(lead + (m, SUB, D))
    # a sub-block's reference: the running sum where it begins
    R = jnp.concatenate([jnp.zeros(lead + (1, D), f32), Gb[..., :-1, -1, :]],
                        axis=-2)                            # [.., m, D]
    up = jnp.exp(Gb - R[..., None, :])                      # <= 1
    # column j seen from sub-block I: exp(R_I - G_j) for j's sub-block
    # J <= I (masked BEFORE the exponential: a later one's is e^320)
    ok = (jnp.arange(m)[:, None] >= jnp.arange(m)[None, :])[:, :, None, None]
    down = jnp.exp(jnp.where(
        ok, R[..., :, None, None, :] - Gb[..., None, :, :, :], -jnp.inf))
    kk = (kz.reshape(lead + (1, m, SUB, D)) * down).astype(cd).reshape(
        lead + (m, c, D))
    kb, qb = kz.reshape(lead + (m, SUB, D)), qz.reshape(lead + (m, SUB, D))
    A = jnp.einsum("nbhIid,nbhIjd->nbhIij", (kb * up).astype(cd), kk,
                   preferred_element_type=f32).reshape(lead + (c, c))
    P = jnp.einsum("nbhIid,nbhIjd->nbhIij", (qb * up).astype(cd), kk,
                   preferred_element_type=f32).reshape(lead + (c, c))
    ii, jj = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    return jnp.where(jj < ii, A * bz[..., None], 0.0), \
        jnp.where(jj <= ii, P, 0.0)


def kda_chunked(q, k, v, g, beta, S, exact=False):
    """The chunked form over every lane's q-block: ``q`` / ``k`` / ``v``
    [B, Q, H, D], ``g`` [B, Q, H, D] float32 (0 on dead rows), ``beta``
    [B, Q, H] float32 (0 with ``k`` 0 on dead rows), ``S`` [B, H, D, D]
    float32 (the lane's carry).  Equal to ``kda_step`` row after row.
    ``exact``: the pairs inside a chunk level by level (``_levels``),
    for a ``g`` with no lower bound (``KDASpec.unbounded``).
    Returns (o [B, Q, H, D] float32, S after the q-block)."""
    B_, Q, H, D = q.shape
    f32 = jnp.float32
    cd = q.dtype                       # the products' operand dtype
    sub = SUB
    c = min(CHUNK, -(-Q // sub) * sub)          # whole sub-blocks
    pad = -Q % c
    if pad:
        # rows of g 0, beta 0 and k 0 past the q-block: they move nothing
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    n = (Q + pad) // c

    def cut(a):
        """[B, Q, H, ...] as chunks [n, B, H, c, ...]."""
        a = a.reshape((B_, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qz, kz, vz = cut(q).astype(f32), cut(k).astype(f32), cut(v).astype(f32)
    bz = cut(beta)                                          # [n, B, H, c]
    G = jnp.cumsum(cut(g), axis=-2)                         # inclusive
    A, P = (_scores_by_level(qz, kz, cut(g), bz, cd) if exact
            else _scores_by_sub_block(qz, kz, G, bz, cd))
    # (I + A) [W | U] = [beta k exp(G) | beta v]: forward substitution
    eG = jnp.exp(G)
    rhs = jnp.concatenate([kz * eG, vz], axis=-1) * bz[..., None]
    # (``unit_diagonal``: A's own diagonal, zeros, is not read)
    WU = jax.lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True)
    end = G[..., -1:, :]                                    # [.., 1, D]
    xs = (WU[..., :D], WU[..., D:], qz * eG, P.astype(cd),
          (kz * jnp.exp(end - G)).astype(cd), jnp.exp(end[..., 0, :]))
    hi = jax.lax.Precision.HIGHEST

    def one(S, x):
        W, U, qg, Pz, kend, dend = x
        # the two products that READ the state take it in float32 at
        # precision highest: a state rounded to bfloat16 as it is read
        # is, a chunk later, a state KEPT in bfloat16
        u = U - jnp.einsum("bhcd,bhdv->bhcv", W, S, precision=hi,
                           preferred_element_type=f32)
        o = jnp.einsum("bhcd,bhdv->bhcv", qg, S, precision=hi,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", Pz, u.astype(cd),
                         preferred_element_type=f32)
        # ONE state update a chunk
        S = S * dend[..., None] + jnp.einsum(
            "bhcd,bhcv->bhdv", kend, u.astype(cd),
            preferred_element_type=f32)
        return S, o

    S, o = jax.lax.scan(one, S, xs)                         # [n, B, H, c, D]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        B_, n * c, H, D)
    return o[:, :Q], S


# how many slots with a q-block wider than one row the chunked form takes
# at a time: a packed wave of 1,024 rows holds three whole chunks of 256
# beside its decoding rows (``retention_decode.WIDE_LANES``)
WIDE_LANES = 3


def takes_kernel(head_dim, q_block):
    """The shape rule: whether a program whose q-blocks are ``q_block``
    rows wide runs the wide slots' chunked form through
    ``kernels/kda_scan`` (else through ``kda_chunked``).  A head of whole
    lane tiles (a head's rows are a column block of the wave's: the
    published model's 128) and a q-block wider than one row that is
    whole chunks.  Static shapes alone decide, so a program is one or
    the other, and the engine can ask the same question of a wave
    (``serve.kda.kernel_chunk_rows``)."""
    from ..kernels._shared import _LANES
    return head_dim % _LANES == 0 and q_block > 1 and q_block % CHUNK == 0


def kda_mixer(sp, q, k, v, g, beta, state, si, q_len, rows=None):
    """One layer's delta rule over the wave's rows: ``q`` / ``k`` / ``v``
    [B, Q, H, D] after the conv and the normalisation, ``g`` [B, Q, H, D]
    and ``beta`` [B, Q, H] float32 (or a packed wave's [1, R, ..] with
    ``rows``).  ``state`` is the manager's set (``KDASpec.state_shapes``),
    of which layer ``si``'s matrix state ``[1, slots, H, D, D]`` is read
    and rewritten.

    The scan never unpacks the wave (``ssm_decode.ssm_mixer``'s
    discipline).  Every slot with ONE live row takes ``kda_step`` at its
    row, the whole batch at once.  The slots with a wider q-block are
    taken ``WIDE_LANES`` at a time, widest first, by a ``while_loop`` that
    gathers their rows and runs ``kda_chunk_scan`` on the states where
    they lie or, where ``takes_kernel`` says no, ``kda_chunked`` on
    slices of them, written back.  Returns (o [.., H D] float32 laid out
    as ``q``, state)."""
    H, D = sp.heads, sp.head_dim
    n_state = len(state) // 2
    mats = state[n_state + si]
    kept = mats.dtype
    Br, Qr = q.shape[:2]
    q_len = jnp.asarray(q_len)
    B_ = q_len.shape[0]
    f32 = jnp.float32
    with jax.named_scope("kda_scan"):
        # the wave's rows as they lie, slot b's from ``start[b]`` on
        Q = Qr if rows is None else rows.q
        start = jnp.arange(B_) * Q if rows is None else rows.start
        q_f, k_f, v_f = (a.reshape(-1, H, D) for a in (q, k, v))
        g_f, b_f = g.reshape(-1, H, D), beta.reshape(-1, H)
        R = q_f.shape[0]
        # the slots with one live row: one step of the recurrence (a
        # slot with none, or with more, has g 0, beta 0 and k 0 here)
        first = jnp.minimum(start, R - 1)
        one = (q_len == 1)[:, None]
        y1, S = kda_step(
            q_f[first], jnp.where(one[..., None], k_f[first], 0),
            v_f[first], jnp.where(one[..., None], g_f[first], 0.0),
            jnp.where(one, b_f[first], 0.0), mats[0].astype(f32))
        y1 = y1.reshape(B_, H * D)
    with jax.named_scope("state_write"):
        mats = S.astype(kept)[None]
    if Q == 1:
        y = y1.reshape(Br, Qr, H * D)
    else:
        with jax.named_scope("kda_scan"):
            # (Q rows more than the wave's: a wide slot's q-block is
            # written back as ONE slice of Q rows from its start)
            y_f = jnp.zeros((R + Q, H * D), f32).at[
                jnp.where(q_len == 1, first, R + Q)].set(y1, mode="drop")
            lanes = math.gcd(WIDE_LANES, B_)     # divides the slots
            order = jnp.argsort(-q_len)                    # widest first
            n_wide = jnp.sum(q_len > 1)
            kernel = takes_kernel(D, Q)

            def wide(carry):
                j0, mats, y_f = carry
                slot = jax.lax.dynamic_slice_in_dim(order, j0 * lanes, lanes)
                # an idle lane (a slot of one row or none, at the order's
                # tail) is dead throughout: its state is written back as
                # it was read
                ql = jnp.where(q_len[slot] > 1, q_len[slot], 0)
                at = start[slot][:, None] + jnp.arange(Q)[None, :]
                live = jnp.arange(Q)[None, :] < ql[:, None]  # [lanes, Q]
                got = jnp.minimum(at, R - 1)
                if kernel:
                    # the lanes' states read and rewritten where they
                    # lie, once, from the rows as gathered (the kernel
                    # masks those past ``ql``: ``kernels/kda_scan``)
                    from ..kernels.kda_scan import kda_chunk_scan
                    yc, mats = kda_chunk_scan(
                        slot, ql, *(a.reshape(R, H * D)[got]
                                    for a in (q, k, v, g)), b_f[got], mats,
                        chunk=CHUNK, sub=SUB, exact=sp.unbounded)
                else:
                    kc = jnp.where(live[..., None, None], k_f[got], 0)
                    gc = jnp.where(live[..., None, None], g_f[got], 0.0)
                    bc = jnp.where(live[..., None], b_f[got], 0.0)
                    # a lane's state by a slice of its own: a gather
                    # over the slots makes the compiler copy the whole
                    # state
                    S0 = jnp.concatenate([jax.lax.dynamic_slice(
                        mats, (0, slot[j], 0, 0, 0), (1, 1, H, D, D))[0]
                        for j in range(lanes)]).astype(f32)
                    yc, Sc = kda_chunked(q_f[got], kc, v_f[got], gc, bc, S0,
                                         exact=sp.unbounded)
                    # every read of the lanes' old states ends here,
                    # before the writes below overwrite them in place
                    yc, Sc = jax.lax.optimization_barrier((yc, Sc))
                    with jax.named_scope("state_write"):
                        for j in range(lanes):
                            mats = jax.lax.dynamic_update_slice(
                                mats, Sc[j].astype(kept)[None, None],
                                (0, slot[j], 0, 0, 0))
                # a lane's live rows into the wave's, by a slice of its
                # own (``retention_mixer``)
                for j in range(lanes):
                    at0 = start[slot[j]]
                    old = jax.lax.dynamic_slice_in_dim(y_f, at0, Q)
                    y_f = jax.lax.dynamic_update_slice_in_dim(
                        y_f, jnp.where(live[j][:, None],
                                       yc[j].reshape(Q, H * D), old), at0, 0)
                return j0 + 1, mats, y_f

            _, mats, y_f = jax.lax.while_loop(
                lambda c: c[0] * lanes < n_wide, wide,
                (jnp.int32(0), mats, y_f))
            y = y_f[:R].reshape(Br, Qr, H * D)
    return y, state[:n_state + si] + (mats,) + state[n_state + si + 1:]


def _low_rank(params, prefix, u, rank):
    """``u W``: ``{prefix}_weight``, or with ``rank`` the two steps
    ``{prefix}_a_weight`` [d, r] then ``{prefix}_b_weight`` [r, ..]."""
    if not rank:
        return u @ params[f"{prefix}_weight"]
    return (u @ params[f"{prefix}_a_weight"]) @ params[f"{prefix}_b_weight"]


def kda_operator(params, us, blk, h, state, si, q_len, rows=None):
    """One layer's KDA over the wave's rows ``h`` ([B, Q, d], or a packed
    wave's [1, R, d] with ``rows``): the front end under ``kda_qkvg`` and
    ``kda_conv`` (``gpt_decode._causal_conv`` over the three tails side
    by side), ``kda_mixer`` over ``state`` (``kda_scan``,
    ``state_write``), the head-wise norm and gate and ``W_o`` under
    ``kda_out``.  No page is written or read.  Returns (h + operator,
    state)."""
    from .gpt_decode import _causal_conv, _norm
    sp = blk.kda
    H, D = sp.heads, sp.head_dim
    f32 = jnp.float32
    tails = state[si]
    Br, Qr = h.shape[:2]
    with jax.named_scope("kda_qkvg"):
        u = _norm(blk, params, f"{us}_ln1", h)
        qkv = u @ params[f"{us}_kda_qkv_weight"]            # [.., 3 H D]
        f = _low_rank(params, f"{us}_kda_f", u, sp.rank).astype(f32) \
            + params[f"{us}_kda_dt_bias"].astype(f32)
        a = jnp.repeat(jnp.exp(params[f"{us}_kda_A_log"].astype(f32)), D)
        if sp.unbounded:
            g = -a * jax.nn.softplus(f)                     # [.., H D] <= 0
        else:
            g = sp.lower_bound * jax.nn.sigmoid(a * f)      # [.., H D] <= 0
        beta = jax.nn.sigmoid(
            (u @ params[f"{us}_kda_beta_weight"]).astype(f32))   # [.., H]
        if sp.beta_scale != 1.0:
            beta = sp.beta_scale * beta
    qkv, last = _causal_conv(qkv, tails[0], params[f"{us}_kda_conv_weight"],
                             q_len, rows, mix="kda_conv", write="kda_conv")
    with jax.named_scope("kda_conv"):
        tails = last.astype(tails.dtype)[None]
        qkv = jax.nn.silu(qkv).reshape(Br, Qr, 3, H, D)
        q = l2norm(qkv[:, :, 0]) * jnp.asarray(D ** -0.5, qkv.dtype)
        k, v = l2norm(qkv[:, :, 1]), qkv[:, :, 2]
    state = state[:si] + (tails,) + state[si + 1:]
    y, state = kda_mixer(sp, q, k, v, g.reshape(Br, Qr, H, D), beta, state,
                         si, q_len, rows)
    with jax.named_scope("kda_out"):
        y = y.reshape(Br, Qr, H, D)
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + blk.norm_eps) \
            * params[f"{us}_kda_norm_scale"].astype(f32).reshape(H, D)
        z = _low_rank(params, f"{us}_kda_gate", u, sp.rank).astype(f32)
        if sp.rank:
            z = z + params[f"{us}_kda_gate_bias"].astype(f32)
        gate = jax.nn.sigmoid(z)
        # one gate a head, or one a column of every head
        gate = gate.reshape(Br, Qr, H, D) if sp.gate_by == "channel" \
            else gate[..., None]
        y = (y * gate).astype(h.dtype).reshape(Br, Qr, H * D)
        h = h + y @ params[f"{us}_kda_out_weight"]
    return h, state
