"""The Mamba-2 (SSD) state-space mixer for the mixed ragged wave, and the
configuration that runs it side by side with grouped-query attention
(the ``falcon_h1`` family).

The mixer, a layer (``u`` the block's normed input, ``H`` heads of
``P`` columns, ``G`` groups of ``N`` state columns, a group serving
``H / G`` heads):

  in     [z | xBC | dt] = ((u * ssm_in) W_in) * mup, widths
         ``H P | H P + 2 G N | H``, ``mup`` the five ``MuP.ssm``
         multipliers spread over the slices z, x, B, C, dt
  conv   xBC <- silu(conv1d(xBC) + b): depthwise, causal, ``K`` taps
         (``gpt_decode._causal_conv``, the short convolution the
         ``lfm2_moe`` block runs), then split x | B | C
  scan   dt <- softplus(dt + dt_bias); A = -exp(A_log), a head;
         S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   ([P, N] a head)
         y_t = S_t C_t + D x_t
  out    y <- RMSNorm_grouped(y * silu(z)) over ``G`` groups (the gate
         first), then ``y W_out * ssm_out``

What a sequence carries from one q-block to its next, a slot a layer:
the conv's last ``K - 1`` inputs ``[K - 1, H P + 2 G N]`` in the pool's
dtype, and the matrix state ``S`` ``[H, P, N]`` in FLOAT32 (a recurrence
that adds ``dt x B^T`` to a decayed sum for a thousand steps loses its
increments in bfloat16's 8 bits).  Both live beside the K/V pool in the
``PagedKVManager`` that owns it, an array a layer of each
(``SSMSpec.state_shapes`` says why), zeroed when a slot is claimed and
handed through the donated step.

One program a bucket serves every kind of row.  A slot with ONE live
row (all of a decode wave's, and the decoding slots of a chunk wave)
takes one step of the recurrence, elementwise in float32, the whole
batch at once: where ``takes_kernel`` says so through the Pallas kernel
``kernels/ssm_step`` (the state read once and written once where it
lies), else through ``ssd_step``, the same step in XLA's operations.  A
slot with a wider q-block takes the CHUNKED form over chunks of
``SSMSpec.chunk`` rows: inside a chunk the products ``C_i B_j^T`` under
the decay ``exp(cum_i - cum_j)``, from chunk to chunk ONE
state update, the first chunk's carry the slot's state and the last
chunk's result written back to it; a wave's few such slots are gathered
``WIDE_LANES`` at a time (``ssm_mixer``), so nothing is computed over
``slots x Q`` padded rows.  A dead row (past ``q_len``) and a dead slot
(``q_len`` 0) have ``dt`` 0: decay 1, increment 0, so the state stays
where it was, bit for bit.  The matrix products take their operands in
the activations' dtype and accumulate in float32; the state is read,
decayed, added to and stored in float32.

Scopes: ``ssm_in``, ``ssm_conv`` (the conv and its tail's write),
``ssm_scan``, ``state_write`` (the matrix state's), ``ssm_out``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class SSMSpec(NamedTuple):
    """The mixer's sizes (the source's ``mamba_*`` keys): ``heads`` of
    ``head_dim`` columns, ``state`` columns a head, ``groups`` of B and
    C, ``conv_kernel`` taps, ``chunk`` rows a chunk of the chunked
    form."""

    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int
    chunk: int

    @property
    def width(self):
        """``d_ssm``: the columns of x, z and y."""
        return self.heads * self.head_dim

    @property
    def conv_width(self):
        """The columns the conv runs over: ``x | B | C``."""
        return self.width + 2 * self.groups * self.state

    @property
    def proj_width(self):
        """``W_in``'s columns: ``z | xBC | dt``."""
        return self.width + self.conv_width + self.heads

    def state_shapes(self, layers):
        """The manager's set of slot states for ``layers`` such layers:
        ((shape without the slot axis, dtype), ...): a layer's conv tail
        in the pool's dtype (None), every layer's first, then a layer's
        matrix state in float32, every layer's.  A layer's state is an
        array of its OWN (a leading axis of 1 where the manager counts
        layers): a wave then rewrites each whole, where one array for
        all layers would be a chain of in-place slice updates, which the
        TPU compiler, short of memory, recomputes, and a recurrence
        applied twice is another state (``PERF.md`` section 6, PR 37)."""
        return (((1, self.conv_kernel - 1, self.conv_width), None),
                ) * layers \
            + (((1, self.heads, self.head_dim, self.state), jnp.float32),
               ) * layers

    def mup_vector(self, mup):
        """The five slice multipliers spread over ``W_in``'s columns."""
        gn = self.groups * self.state
        widths = (self.width, self.width, gn, gn, self.heads)
        return jnp.concatenate([jnp.full((w,), m, jnp.float32)
                                for w, m in zip(widths, mup.ssm)])


def ssd_step(x, dt, A, Bm, Cm, S):
    """One step of the recurrence for every slot: ``x`` [B, H, P], ``dt``
    [B, H] (0: the slot does not move), ``A`` [H], ``Bm`` / ``Cm`` [B, G,
    N], ``S`` [B, H, P, N] float32.  Returns (y [B, H, P] float32 without
    the ``D x`` term, S)."""
    hg = x.shape[1] // Bm.shape[1]
    f32 = jnp.float32
    Bh = jnp.repeat(Bm.astype(f32), hg, axis=1)            # [B, H, N]
    Ch = jnp.repeat(Cm.astype(f32), hg, axis=1)
    a = jnp.exp(dt * A)                                    # [B, H]
    S = S * a[:, :, None, None] \
        + (dt[:, :, None] * x.astype(f32))[..., None] * Bh[:, :, None, :]
    return jnp.sum(S * Ch[:, :, None, :], axis=-1), S


def takes_kernel(spec):
    """The shape rule: whether a program of ``spec``'s mixers runs the
    one-row slots' step through ``kernels/ssm_step`` (else through
    ``ssd_step``).  A state whose columns are whole lane tiles and whose
    rows are whole sublane tiles (both published models'; the state is
    float32 whatever the model).  Static shapes alone decide, so a
    program is one or the other, and the engine can ask the same
    question of a wave (``serve.ssm.kernel_slot_steps``)."""
    from ..kernels._shared import _LANES
    return spec.state % _LANES == 0 and spec.head_dim % 8 == 0


def ssd_chunked(x, dt, A, Bm, Cm, S, chunk):
    """The chunked form over every slot's q-block: ``x`` [B, Q, H, P],
    ``dt`` [B, Q, H] (0 on dead rows), ``A`` [H], ``Bm`` / ``Cm`` [B, Q,
    G, N], ``S`` [B, H, P, N] float32 (the slot's carry).  Equal to
    ``ssd_step`` row after row.  Returns (y [B, Q, H, P] float32
    without ``D x``, S after the q-block)."""
    B_, Q, H, P = x.shape
    G, N = Bm.shape[2:]
    hg = H // G
    f32 = jnp.float32
    cd = x.dtype                       # the products' operand dtype
    c = min(int(chunk), Q)
    pad = -Q % c
    if pad:
        # rows of dt 0 past the q-block: they move nothing
        x, dt, Bm, Cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (v.ndim - 2)) for v in (x, dt, Bm, Cm))
    tri = jnp.tril(jnp.ones((c, c), bool))
    ys = []
    for z0 in range(0, Q + pad, c):
        xz, dtz = x[:, z0:z0 + c], dt[:, z0:z0 + c]
        Bz, Cz = Bm[:, z0:z0 + c], Cm[:, z0:z0 + c]
        dth = dtz.transpose(0, 2, 1)                       # [B, H, c]
        cum = jnp.cumsum(dth * A[None, :, None], axis=-1)  # inclusive
        # inside the chunk: row i reads row j <= i under the decay
        # exp(cum_i - cum_j) (masked BEFORE the exponential: above the
        # diagonal the difference is positive and may overflow)
        cb = jnp.einsum("bign,bjgn->bgij", Cz, Bz,
                        preferred_element_type=f32)        # [B, G, c, c]
        decay = jnp.exp(jnp.where(
            tri, cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))
        w = jnp.repeat(cb, hg, axis=1) * decay * dth[:, :, None, :]
        y = jnp.einsum("bhij,bjhp->bihp", w.astype(cd), xz,
                       preferred_element_type=f32)
        # from the carry: C_i S under the decay since the chunk began
        Sg = S.reshape(B_, G, hg, P, N)
        y0 = jnp.einsum("bign,bgkpn->bigkp", Cz.astype(f32), Sg,
                        preferred_element_type=f32).reshape(B_, c, H, P)
        y = y + y0 * jnp.exp(cum).transpose(0, 2, 1)[..., None]
        # ONE state update a chunk: every row's increment under the
        # decay that is left to the chunk's end
        left = jnp.exp(cum[:, :, -1:] - cum) * dth         # [B, H, c]
        xw = (xz.astype(f32) * left.transpose(0, 2, 1)[..., None]
              ).astype(cd).reshape(B_, c, G, hg, P)
        Sc = jnp.einsum("bjgkp,bjgn->bgkpn", xw, Bz,
                        preferred_element_type=f32).reshape(B_, H, P, N)
        S = S * jnp.exp(cum[:, :, -1])[:, :, None, None] + Sc
        ys.append(y)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    return y[:, :Q], S


def gated_group_norm(y, z, scale, groups, eps):
    """``RMSNorm(y * silu(z))`` over ``groups`` groups of the last axis,
    statistics in float32 (the gate FIRST: ``mamba_norm_before_gate``
    false)."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shp = g.shape
    g = g.reshape(shp[:-1] + (groups, shp[-1] // groups))
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    return g.reshape(shp) * scale.astype(jnp.float32)


# how many slots with a q-block wider than one row the chunked form
# takes at a time: a packed chunk wave holds a few (its rows are capped,
# ``gpt_decode.wave_rows``), so one pass is the rule
WIDE_LANES = 4


def ssm_mixer(params, us, blk, u, state, si, q_len, rows=None):
    """One layer's mixer over the wave's rows ``u`` (the block's normed
    input: [B, Q, d], or a packed wave's [1, R, d] with ``rows``).
    ``state`` is the manager's set (``SSMSpec.state_shapes``: every
    layer's conv tail ``[1, slots, K - 1, conv_width]``, then every
    layer's matrix state ``[1, slots, H, P, N]`` float32), of which
    layer ``si``'s two are read and rewritten whole.

    The scan never unpacks the wave.  Every slot with ONE live row (all
    of a decode wave's) takes one step at its row, the whole batch at
    once (``kernels/ssm_step`` by ``takes_kernel``, else ``ssd_step``).
    The slots with a wider q-block (a chunk wave's few) are taken
    ``WIDE_LANES`` at a time, widest first, by a ``while_loop`` that
    gathers their rows and states, runs ``ssd_chunked`` and scatters both
    back: one pass for up to ``WIDE_LANES`` chunks, as many as it takes
    otherwise, so that the cost follows the live rows and not ``slots x
    Q``.  Returns (the mixer's output times ``ssm_out``, laid out as
    ``u``; state)."""
    from .gpt_decode import MuP, _causal_conv
    sp, mup = blk.ssm, blk.mup or MuP()
    H, P, N, G = sp.heads, sp.head_dim, sp.state, sp.groups
    d_ssm, gn = sp.width, sp.groups * sp.state
    n_state = len(state) // 2
    tails, mats = state[si], state[n_state + si]
    Br, Qr = u.shape[:2]
    q_len = jnp.asarray(q_len)
    B_ = q_len.shape[0]
    f32 = jnp.float32
    with jax.named_scope("ssm_in"):
        proj = ((u * mup.ssm_in) @ params[f"{us}_ssm_in_weight"]) \
            * sp.mup_vector(mup).astype(u.dtype)
        z = proj[..., :d_ssm]
        xbc = proj[..., d_ssm:d_ssm + sp.conv_width]
        dt = proj[..., d_ssm + sp.conv_width:]
    xbc, last = _causal_conv(xbc, tails[0], params[f"{us}_ssm_conv_weight"],
                             q_len, rows, mix="ssm_conv", write="ssm_conv")
    with jax.named_scope("ssm_conv"):
        tails = last.astype(tails.dtype)[None]
        xbc = jax.nn.silu(xbc + params[f"{us}_ssm_conv_bias"])
    A = -jnp.exp(params[f"{us}_ssm_A_log"].astype(f32))
    D = params[f"{us}_ssm_D"].astype(f32)

    def split(v):
        """``x | B | C`` of rows ``v`` [..., conv_width]."""
        lead = v.shape[:-1]
        return (v[..., :d_ssm].reshape(lead + (H, P)),
                v[..., d_ssm:d_ssm + gn].reshape(lead + (G, N)),
                v[..., d_ssm + gn:].reshape(lead + (G, N)))

    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(f32) + params[f"{us}_ssm_dt_bias"])
        # the wave's rows as they lie, slot b's from ``start[b]`` on
        Q = Qr if rows is None else rows.q
        start = jnp.arange(B_) * Q if rows is None else rows.start
        xbc_f, dt_f = xbc.reshape(-1, sp.conv_width), dt.reshape(-1, H)
        R = xbc_f.shape[0]
        # the slots with one live row: one step of the recurrence (a
        # slot with none, or with more, has dt 0 here and stays)
        first = jnp.minimum(start, R - 1)
        x1, B1, C1 = split(xbc_f[first])
        dt1 = jnp.where((q_len == 1)[:, None], dt_f[first], 0.0)
        kernel = takes_kernel(sp)
        if kernel:
            # the state decayed, added to, read out and stored in ONE
            # pass where it lies
            from ..kernels.ssm_step import ssm_step
            y1, mats = ssm_step(x1, dt1, A, B1, C1, mats)
        else:
            y1, S = ssd_step(x1, dt1, A, B1, C1, mats[0])
        y1 = (y1 + D[:, None] * x1.astype(f32)).reshape(B_, d_ssm)
    if not kernel:
        with jax.named_scope("state_write"):
            mats = S[None]
    if Q == 1:
        y = y1.reshape(Br, Qr, d_ssm)
    else:
        with jax.named_scope("ssm_scan"):
            y_f = jnp.zeros((R, d_ssm), f32).at[
                jnp.where(q_len == 1, first, R)].set(y1, mode="drop")
            lanes = math.gcd(WIDE_LANES, B_)     # divides the slots
            order = jnp.argsort(-q_len)                    # widest first
            n_wide = jnp.sum(q_len > 1)
            where = lambda slot: (0, slot, 0, 0, 0)         # noqa: E731

            def wide(carry):
                k, mats, y_f = carry
                slot = jax.lax.dynamic_slice_in_dim(order, k * lanes, lanes)
                # an idle lane (a slot of one row or none, at the order's
                # tail) has dt 0 throughout: its state is written back
                # as it was read
                ql = jnp.where(q_len[slot] > 1, q_len[slot], 0)
                at = start[slot][:, None] + jnp.arange(Q)[None, :]
                live = jnp.arange(Q)[None, :] < ql[:, None]  # [lanes, Q]
                got = jnp.minimum(at, R - 1)
                xc, Bc, Cc = split(xbc_f[got])
                dtc = jnp.where(live[..., None], dt_f[got], 0.0)
                # a lane's state by a slice of its own: a gather over
                # the slots makes the compiler copy the whole state
                S0 = jnp.concatenate([jax.lax.dynamic_slice(
                    mats, where(slot[j]), (1, 1, H, P, N))[0]
                    for j in range(lanes)])
                yc, Sc = ssd_chunked(xc, dtc, A, Bc, Cc, S0, sp.chunk)
                yc = yc + D[:, None] * xc.astype(f32)
                # every read of the lanes' old states ends here, before
                # the writes below overwrite them in place: the compiler
                # may read a slice again rather than keep it, and a slice
                # read again after its write is the new state
                yc, Sc = jax.lax.optimization_barrier((yc, Sc))
                y_f = y_f.at[jnp.where(live, at, R).reshape(-1)].set(
                    yc.reshape(lanes * Q, d_ssm), mode="drop")
                with jax.named_scope("state_write"):
                    for j in range(lanes):
                        mats = jax.lax.dynamic_update_slice(
                            mats, Sc[j][None, None], where(slot[j]))
                return k + 1, mats, y_f

            _, mats, y_f = jax.lax.while_loop(
                lambda c: c[0] * lanes < n_wide, wide,
                (jnp.int32(0), mats, y_f))
            y = y_f.reshape(Br, Qr, d_ssm)
    with jax.named_scope("ssm_out"):
        y = gated_group_norm(y, z, params[f"{us}_ssm_norm_scale"], G,
                             blk.norm_eps).astype(u.dtype)
        y = (y @ params[f"{us}_ssm_out_weight"]) * mup.ssm_out
    return y, (state[:si] + (tails,) + state[si + 1:n_state + si]
               + (mats,) + state[n_state + si + 1:])


# ------------------------- the configuration ------------------------- #


# what each weight product's output is, in units of its input's RMS,
# at the seeded weights (``init_ssm_hybrid_params``): the weight's
# deviation is ``gain / (multiplier * sqrt(fan_in))``, so that the
# multipliers a trained checkpoint was given do not make a branch
# vanish at random weights
DEFAULT_GAINS = {
    "embedding": 1.0, "attn_q": 1.25, "attn_k": 1.25, "attn_v": 1.0,
    "attn_out": 2.0, "ssm_z": 1.0, "ssm_x": 1.0, "ssm_B": 1.0,
    "ssm_C": 1.0, "ssm_dt": 0.5, "ssm_conv": 1.0, "ssm_conv_bias": 0.1,
    "ssm_out": 0.5, "mlp_gate": 1.0, "mlp_up": 1.0, "mlp_down": 1.0,
    "lm_head": 1.0}


class SSMHybridConfig:
    """A decoder whose every layer runs a Mamba-2 mixer AND grouped-query
    attention on one RMSNorm, their outputs summed into the residual,
    then a dense SwiGLU; forward multipliers throughout; untied head:
    built from the source's own ``config.json`` keys (the ``falcon_h1``
    family's names).  It yields the jit-static ``BlockSpec`` the mixed
    wave reads; the engine takes the rest from the attributes a
    ``GPTConfig`` has too.  Values it cannot run raise: biases on the
    projections, ``mamba_rms_norm`` false, ``mamba_norm_before_gate``
    true, a tied head, ``attn_layer_indices`` (every layer holds both
    mixers), a RoPE scaling, sizes that do not divide."""

    def __init__(self, *, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, num_key_value_heads, head_dim,
                 intermediate_size, mamba_d_ssm, mamba_n_heads,
                 mamba_d_head, mamba_d_state, mamba_n_groups, mamba_d_conv,
                 mamba_chunk_size=128, mamba_conv_bias=True,
                 mamba_proj_bias=False, mamba_rms_norm=True,
                 mamba_norm_before_gate=False, attention_bias=False,
                 mlp_bias=False, projectors_bias=False,
                 tie_word_embeddings=False, attn_layer_indices=None,
                 rope_scaling=None, rope_theta=1e11, rms_norm_eps=1e-5,
                 embedding_multiplier=1.0, attention_in_multiplier=1.0,
                 attention_out_multiplier=1.0, key_multiplier=1.0,
                 ssm_in_multiplier=1.0, ssm_out_multiplier=1.0,
                 ssm_multipliers=(1.0,) * 5, mlp_multipliers=(1.0, 1.0),
                 lm_head_multiplier=1.0, max_position_embeddings=262144,
                 **ignored):
        bad = [k for k, v in (
            ("mamba_proj_bias", mamba_proj_bias),
            ("attention_bias", attention_bias), ("mlp_bias", mlp_bias),
            ("projectors_bias", projectors_bias),
            ("mamba_norm_before_gate", mamba_norm_before_gate),
            ("tie_word_embeddings", tie_word_embeddings),
            ("attn_layer_indices", attn_layer_indices),
            ("rope_scaling", rope_scaling)) if v]
        if not mamba_rms_norm:
            bad.append("mamba_rms_norm=False")
        if not mamba_conv_bias:
            bad.append("mamba_conv_bias=False")
        if bad:
            raise ValueError(f"SSMHybridConfig cannot run {bad}")
        if mamba_d_ssm != mamba_n_heads * mamba_d_head \
                or mamba_n_heads % mamba_n_groups \
                or num_attention_heads % num_key_value_heads \
                or head_dim % 2 or mamba_d_conv < 2 \
                or len(ssm_multipliers) != 5 or len(mlp_multipliers) != 2:
            raise ValueError(
                f"SSMHybridConfig: sizes do not fit: d_ssm={mamba_d_ssm}, "
                f"{mamba_n_heads} heads of {mamba_d_head} in "
                f"{mamba_n_groups} groups, {num_attention_heads} over "
                f"{num_key_value_heads} heads of {head_dim}, "
                f"{mamba_d_conv} taps, {len(ssm_multipliers)} and "
                f"{len(mlp_multipliers)} multipliers")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.intermediate_size = int(intermediate_size)
        self.max_position_embeddings = int(max_position_embeddings)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.ssm = SSMSpec(int(mamba_n_heads), int(mamba_d_head),
                           int(mamba_d_state), int(mamba_n_groups),
                           int(mamba_d_conv), int(mamba_chunk_size))
        from .gpt_decode import MuP
        self.mup = MuP(
            embedding=float(embedding_multiplier),
            attention_in=float(attention_in_multiplier),
            attention_out=float(attention_out_multiplier),
            key=float(key_multiplier), ssm_in=float(ssm_in_multiplier),
            ssm_out=float(ssm_out_multiplier),
            ssm=tuple(float(m) for m in ssm_multipliers),
            mlp_gate=float(mlp_multipliers[0]),
            mlp_down=float(mlp_multipliers[1]),
            lm_head=float(lm_head_multiplier))

    @classmethod
    def from_hf(cls, config):
        """From a ``config.json`` dict (keys it does not know are
        ignored; the ones it cannot run raise)."""
        return cls(**config)

    def block_spec(self):
        from .gpt_decode import BlockSpec
        return BlockSpec(
            norm="rmsnorm", norm_eps=self.rms_norm_eps, positions="rope",
            rope_theta=self.rope_theta, attention="gqa", bias=False,
            kv_heads=self.num_key_value_heads,
            ops=("attention+ssm",) * self.num_hidden_layers,
            ffn="swiglu", head="untied", head_dim=self.head_dim,
            ssm=self.ssm, mup=self.mup)

    def param_shapes(self, name="fh1"):
        """{leaf: shape} of the serving parameter dict."""
        d, dh, f = self.hidden_size, self.head_dim, self.intermediate_size
        hq, hkv, sp = (self.num_attention_heads, self.num_key_value_heads,
                       self.ssm)
        shapes = {f"{name}_wte_table": (self.vocab_size, d),
                  f"{name}_ln_f_scale": (d,),
                  f"{name}_lm_head_weight": (d, self.vocab_size)}
        for i in range(self.num_hidden_layers):
            us = f"{name}_h{i}"
            shapes.update({
                f"{us}_ln1_scale": (d,), f"{us}_ln2_scale": (d,),
                f"{us}_attn_q_weight": (d, hq * dh),
                f"{us}_attn_k_weight": (d, hkv * dh),
                f"{us}_attn_v_weight": (d, hkv * dh),
                f"{us}_attn_proj_weight": (hq * dh, d),
                f"{us}_ssm_in_weight": (d, sp.proj_width),
                f"{us}_ssm_conv_weight": (sp.conv_kernel, sp.conv_width),
                f"{us}_ssm_conv_bias": (sp.conv_width,),
                f"{us}_ssm_dt_bias": (sp.heads,),
                f"{us}_ssm_A_log": (sp.heads,),
                f"{us}_ssm_D": (sp.heads,),
                f"{us}_ssm_norm_scale": (sp.width,),
                f"{us}_ssm_out_weight": (sp.width, d),
                f"{us}_ffn_gate_weight": (d, f),
                f"{us}_ffn_up_weight": (d, f),
                f"{us}_ffn_down_weight": (f, d)})
        return shapes


# float32 whatever the serving dtype: the recurrence's own constants
F32_LEAVES = ("_ssm_dt_bias", "_ssm_A_log", "_ssm_D")


def init_recurrence_constant(name, key, shape, dt_range, a_range):
    """The seeded value of one of ``F32_LEAVES`` by the family's
    initialisation (``D`` 1, ``A_log`` the logarithm of ``A`` uniform in
    ``a_range``, ``dt_bias`` the inverse softplus of ``dt`` log-uniform
    in ``dt_range``), float32; None for any other leaf."""
    if name.endswith("_ssm_D"):
        return jnp.ones(shape, jnp.float32)
    if name.endswith("_ssm_A_log"):
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, a_range[0], a_range[1]))
    if name.endswith("_ssm_dt_bias"):
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(dt_range[0]),
            math.log(dt_range[1])))
        return dt + jnp.log(-jnp.expm1(-dt))
    return None


def init_ssm_hybrid_params(config, name="fh1", seed=0, gains=None,
                           dtype=jnp.float32, dt_range=(0.001, 0.1),
                           a_range=(1.0, 16.0)):
    """Seeded random serving params for an ``SSMHybridConfig``, made on
    the device in one jitted call.  Every weight matrix is
    ``normal(gain / (multiplier * sqrt(fan_in)))`` with the multiplier
    the forward applies to its product (``DEFAULT_GAINS``; ``gains``
    overrides entries), ``W_in``'s five slices each by their own; norm
    scales 1; and the recurrence's constants by the family's
    initialisation: ``dt`` log-uniform in ``dt_range`` (``dt_bias`` its
    inverse softplus), ``A`` uniform in ``a_range`` (``A_log`` its
    logarithm), ``D`` 1, all three float32."""
    g = dict(DEFAULT_GAINS, **(gains or {}))
    c, sp, m = config, config.ssm, config.mup
    d, f = c.hidden_size, c.intermediate_size
    gn = sp.groups * sp.state
    root = math.sqrt
    dev = {
        "_wte_table": g["embedding"] / m.embedding,
        "_lm_head_weight": g["lm_head"] / (m.lm_head * root(d)),
        "_attn_q_weight": g["attn_q"] / (m.attention_in * root(d)),
        "_attn_k_weight": g["attn_k"] / (m.attention_in * m.key * root(d)),
        "_attn_v_weight": g["attn_v"] / (m.attention_in * root(d)),
        "_attn_proj_weight": g["attn_out"] / (
            m.attention_out * root(c.num_attention_heads * c.head_dim)),
        "_ssm_conv_weight": g["ssm_conv"] / root(sp.conv_kernel),
        "_ssm_conv_bias": g["ssm_conv_bias"],
        "_ssm_out_weight": g["ssm_out"] / (m.ssm_out * root(sp.width)),
        "_ffn_gate_weight": g["mlp_gate"] / (m.mlp_gate * root(d)),
        "_ffn_up_weight": g["mlp_up"] / root(d),
        "_ffn_down_weight": g["mlp_down"] / (m.mlp_down * root(f)),
    }
    widths = (sp.width, sp.width, gn, gn, sp.heads)
    slices = [g[k] / (m.ssm_in * ms * root(d)) for k, ms in zip(
        ("ssm_z", "ssm_x", "ssm_B", "ssm_C", "ssm_dt"), m.ssm)]
    shapes = config.param_shapes(name)

    def make(key):
        out = {}
        in_cols = jnp.concatenate([jnp.full((w,), s, jnp.float32)
                                   for w, s in zip(widths, slices)])
        for k, (n, shape) in zip(jax.random.split(key, len(shapes)),
                                 sorted(shapes.items())):
            constant = init_recurrence_constant(n, k, shape, dt_range,
                                                a_range)
            if n.endswith("_scale"):
                out[n] = jnp.ones(shape, dtype)
            elif constant is not None:
                out[n] = constant
            elif n.endswith("_ssm_in_weight"):
                out[n] = (jax.random.normal(k, shape, jnp.float32)
                          * in_cols).astype(dtype)
            else:
                s = next(v for suffix, v in dev.items()
                         if n.endswith(suffix))
                out[n] = (s * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
