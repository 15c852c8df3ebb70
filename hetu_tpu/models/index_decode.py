"""The learned indexer of a latent attention that reads a SUBSET of its
sequence (``gpt_decode.IndexSpec``; the DeepSeek-V3.2 family's lightning
indexer), as the mixed ragged wave traces it for one "latent_attention"
layer, called by ``gpt_decode._latent_attention``:

  index_select   the indexer's projections (scope ``mla_index``), an
                 index key a row into the index-key pool beside the
                 latent rows (``index_write``) and every row's scores
                 against the keys its slot holds (``index_score``)
  chosen_mask    the ``topk`` largest of a row's scores as a 0 / 1 mask
                 over its slot's positions (``index_topk``), which the
                 attention walks its pages under
                 (``ragged_paged_mla_rows(allowed=)``)

A model's configuration class (``sparse_latent.SparseLatentConfig``)
yields the spec; nothing here knows a model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# query rows of one slot scored against the slot's keys at once
# (``[rows, index heads, positions]`` float32: 64 x 64 x 12,800 is 0.21 GB)
INDEX_ROW_BLOCK = 64


def _index_scores(q, w, k):
    """``sum_j w_j relu(q_j . k_s)``: q [n, J, D], w [n, J] float32, k
    [n, S, D] (a set of keys a row) or [S, D] (one for all) -> [n, S]
    float32."""
    eq = "njd,nsd->njs" if k.ndim == 3 else "njd,sd->njs"
    s = jnp.einsum(eq, q, k, preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w[:, :, None], axis=1)


def index_select(params, us, blk, la, x, cq, index_pool, i, wblk, woff,
                 posns, q_len, block_tables, rows, rope):
    """The indexer of one "latent_attention" layer over the wave's rows
    (``x`` the normed residual, ``cq`` the query's low-rank norm, both
    ``[B, Q, ..]`` or a packed wave's ``[1, R, ..]`` with ``rows``):

    * ``mla_index``: ``q_j = RoPE(c_q W_Iq^j)`` (``n_heads`` of
      ``head_dim``, the first ``rope_dim`` columns rotated by the
      layer's own frequencies), ``k = RoPE(LayerNorm(x W_Ik))``, ``w =
      x W_Iw / sqrt(n_heads x head_dim)`` in float32;
    * ``index_write``: ``k`` into ``index_pool[i]`` at the blocks the
      latent rows went to;
    * ``index_score``: every row against the keys its SLOT holds
      (gathered through ``block_tables``): each slot's first row in one
      batched product (all there is of a decode wave), the further rows
      of slots that carry more in a loop over blocks of
      ``INDEX_ROW_BLOCK`` rows of one slot, as many as the wave has;
    Returns ((scores [rows, S] float32, seen [rows, S]: the positions a
    row sees), index_pool), ``rows`` the wave's rows as they lie,
    flattened; ``chosen_mask`` (scope ``index_topk``) takes the ``topk``
    largest."""
    from .gpt_decode import NEG_INF, _ln, _rope
    ix = la.index
    J, D, rd = ix.n_heads, ix.head_dim, ix.rope_dim
    inv, factor = rope
    Br, Qr = x.shape[:2]
    B = q_len.shape[0]

    def rotated(v):
        return jnp.concatenate(
            [_rope(v[..., :rd], posns, blk.rope_theta, inv, factor),
             v[..., rd:]], axis=-1)

    with jax.named_scope("mla_index"):
        qi = rotated((cq @ params[f"{us}_attn_index_q_weight"]).reshape(
            Br, Qr, J, D))
        ki = rotated(_ln(x @ params[f"{us}_attn_index_k_weight"],
                         params[f"{us}_attn_index_k_norm_scale"],
                         params[f"{us}_attn_index_k_norm_bias"],
                         blk.norm_eps))
        w = (x @ params[f"{us}_attn_index_w_weight"]).astype(
            jnp.float32) * (J * D) ** -0.5
    with jax.named_scope("index_write"):
        index_pool = index_pool.at[i, wblk, woff].set(
            ki.astype(index_pool.dtype))
    n_rows = Br * Qr
    qf, wf = qi.reshape(n_rows, J, D), w.reshape(n_rows, J)
    at = posns.reshape(n_rows)
    if rows is None:
        Q = Qr
        start = jnp.arange(B) * Q
        valid = (jnp.arange(Q)[None, :] < q_len[:, None]).reshape(n_rows)
    else:
        Q, start, valid = rows.q, rows.start, rows.live
    T, bs = block_tables.shape[1], index_pool.shape[2]
    S = T * bs
    keys = index_pool[i]                                    # [N, bs, D]
    with jax.named_scope("index_score"):
        first = jnp.minimum(start, n_rows - 1)
        scores = _index_scores(qf[first], wf[first],
                               keys[block_tables].reshape(B, S, D))
        if Q > 1:
            qb = min(Q, INDEX_ROW_BLOCK)
            blocks = jnp.where(q_len > 1, -(-q_len // qb), 0)
            ends = jnp.cumsum(blocks)
            qp = jnp.pad(qf, ((0, qb), (0, 0), (0, 0)))
            wp = jnp.pad(wf, ((0, qb), (0, 0)))
            # a dead slot's first row goes past the wave's rows
            acc = jnp.full((n_rows + qb, S), NEG_INF, jnp.float32).at[
                jnp.where(q_len > 0, start, n_rows)].set(scores)

            def block(t, acc):
                b = jnp.sum(t >= ends)
                k = t - (ends[b] - blocks[b])
                r0 = start[b] + k * qb
                sc = _index_scores(
                    jax.lax.dynamic_slice_in_dim(qp, r0, qb),
                    jax.lax.dynamic_slice_in_dim(wp, r0, qb),
                    keys[block_tables[b]].reshape(S, D))
                own = (k * qb + jnp.arange(qb) < q_len[b])[:, None]
                old = jax.lax.dynamic_slice(acc, (r0, 0), (qb, S))
                return jax.lax.dynamic_update_slice(
                    acc, jnp.where(own, sc, old), (r0, 0))

            scores = jax.lax.fori_loop(0, ends[-1], block, acc)[:n_rows]
    seen = (jnp.arange(S)[None, :] <= at[:, None]) & valid[:, None]
    return (scores, seen), index_pool


def chosen_mask(select, topk):
    """``allowed`` [rows, S] float32 (1: the row reads the position) of
    ``index_select``'s scores: the ``topk`` largest among the positions
    a row sees (ties by lower position), all of them while it sees no
    more, found without a sort.  A row's ``topk``-th largest score is
    found EXACTLY by bisection over the scores' bit patterns (32 passes of a compare and
    a count over ``[rows, S]``: an order-preserving map of float32 onto
    unsigned integers, the threshold built bit by bit from the top);
    what lies above it is chosen, and of what equals it the lowest
    positions that fill the ``topk`` (a running count)."""
    scores, seen = select
    with jax.named_scope("index_topk"):
        K = min(topk, scores.shape[1])
        bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
        # ascending in the float's order; what a row does not see is 0,
        # below every score (a finite score maps above 0)
        key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
        key = jnp.where(seen, key, jnp.uint32(0))

        def bit(i, t):
            cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            enough = jnp.sum(key >= cand[:, None], axis=1) >= K
            return jnp.where(enough, cand, t)

        kth = jax.lax.fori_loop(0, 32, bit,
                                jnp.zeros(key.shape[0], jnp.uint32))
        above = key > kth[:, None]
        equal = seen & (key == kth[:, None])
        room = K - jnp.sum(above, axis=1)
        allowed = seen & (above | (equal & (
            jnp.cumsum(equal, axis=1, dtype=jnp.int32) <= room[:, None])))
    return allowed.astype(jnp.float32)
