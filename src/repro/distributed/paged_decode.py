"""Sequence-parallel paged decode attention — the Tiara one-round path.

Baseline decode shards the KV pool over the dp axes and lets GSPMD handle
``k_pages[block_tables]``; XLA falls back to masked gathers + full
all-reduces of the gathered KV (PB-scale collectives at 32k, see
EXPERIMENTS.md §Perf cell 1).  This module applies the paper's move —
*ship the request to the memory, not the memory to the request*:

  * pages are sharded over ALL mesh axes (each chip owns pool/chips
    whole pages and never sends them anywhere);
  * every chip resolves the block table against its own pages
    (register-chained load: table entry -> local page) and computes a
    partial flash-attention over the tokens it owns;
  * partials merge with one tiny online-softmax reduction
    (pmax/psum of (B, H, D) accumulators) — the only collective.

Per layer the wire carries O(B x QH x D) floats instead of O(KV bytes).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.compile import masked_row_gather

NEG = -1e30


def _partial_paged_attention(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        bt_local: jax.Array, lengths: jax.Array, *,
        base_page: jax.Array, scale: float
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Flash partial over the locally-owned pages.

    q (B, KVH, G, D); k/v_pages (pp_local, page, KVH, D); bt_local
    (B, maxp) GLOBAL page ids; returns unnormalized (acc, m, l)."""
    b, kvh, group, hd = q.shape
    pp_local, page, _, _ = k_pages.shape
    maxp = bt_local.shape[1]

    loff = bt_local - base_page
    mine = (loff >= 0) & (loff < pp_local)
    # the compiled gather-chain superoperator: block table -> local pages
    k = masked_row_gather(k_pages, loff)     # (B, maxp, page, KVH, D)
    v = masked_row_gather(v_pages, loff)
    s = jnp.einsum("bhgd,bmphd->bhgmp", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    pos = (jnp.arange(maxp)[:, None] * page
           + jnp.arange(page)[None, :])[None]            # (1, maxp, page)
    valid = (pos < lengths[:, None, None]) & mine[..., None]
    s = jnp.where(valid[:, None, None], s, NEG)
    m = jnp.max(s, axis=(-2, -1))                        # (B, KVH, G)
    p = jnp.exp(s - m[..., None, None])
    p = jnp.where(valid[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=(-2, -1))
    acc = jnp.einsum("bhgmp,bmphd->bhgd", p, v.astype(jnp.float32))
    return acc, m, l


def _partial_paged_attention_sliced(
        q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
        bt: jax.Array, lengths: jax.Array, *,
        base_page: jax.Array, base_local: jax.Array, maxp: int,
        scale: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Contiguous-slab variant.  With the pool laid out (dp-major,
    model-minor) and per-sequence page slabs, each model rank's
    ``pp_local`` pages form one contiguous chunk of exactly ONE local
    sequence (requires B_local <= model size, true for the assigned decode
    shapes).  The rank attends its own pages against that sequence's
    query only — zero redundant HBM traffic — and contributes
    -inf/0 partials for every other sequence."""
    b, kvh, group, hd = q.shape
    pp_local, page, _, _ = k_pages.shape
    assert pp_local <= maxp and maxp % pp_local == 0, \
        "contiguous decode requires B_local <= model-axis size"
    seq_local = base_local // maxp                       # traced scalar
    col0 = base_local % maxp
    btrow = lax.dynamic_index_in_dim(bt, seq_local, 0, keepdims=False)
    cols = lax.dynamic_slice_in_dim(btrow, col0, pp_local, 0)
    loff = cols - base_page
    mine = (loff >= 0) & (loff < pp_local)
    k = masked_row_gather(k_pages, loff)           # (pp, page, KVH, D)
    v = masked_row_gather(v_pages, loff)
    qrow = lax.dynamic_index_in_dim(q, seq_local, 0, keepdims=False)
    length = lax.dynamic_index_in_dim(lengths, seq_local, 0,
                                      keepdims=False)
    s = jnp.einsum("hgd,mphd->hgmp", qrow.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    pos = ((col0 + jnp.arange(pp_local))[:, None] * page
           + jnp.arange(page)[None, :])
    valid = (pos < length) & mine[:, None]
    s = jnp.where(valid[None, None], s, NEG)
    m1 = jnp.max(s, axis=(-2, -1))                        # (KVH, G)
    p = jnp.exp(s - m1[..., None, None])
    p = jnp.where(valid[None, None], p, 0.0)
    l1 = jnp.sum(p, axis=(-2, -1))
    acc1 = jnp.einsum("hgmp,mphd->hgd", p, v.astype(jnp.float32))
    # scatter the single-sequence partial into the (B_local, ...) slots
    onehot = (jnp.arange(b) == seq_local)
    m = jnp.where(onehot[:, None, None], m1[None], NEG)
    l = jnp.where(onehot[:, None, None], l1[None], 0.0)
    acc = jnp.where(onehot[:, None, None, None], acc1[None], 0.0)
    return acc, m, l


def sharded_paged_attention(mesh: Mesh, dp_axes: Tuple[str, ...],
                            model_axis: str = "model", *,
                            contiguous: bool = False,
                            batch_sharded: bool = True) -> Callable[..., Any]:
    """Builds fn(q, k_pages, v_pages, new_k, new_v, bt, lengths) -> (out,
    k_pages, v_pages): appends the new token's KV to its owning chip and
    attends, all pages staying local.

    q: (B, QH, D); pages: (pool, page, KVH, D) sharded over
    (dp..., model) on the pool dim; bt: (B, maxp); lengths: (B,).

    ``contiguous`` (§Perf cell 1, iteration 2): the serving allocator
    gives every sequence a per-rank-contiguous page slab (identity layout:
    model rank m owns block-table columns [m*maxp/M, (m+1)*maxp/M)), so
    each rank slices its own 1/M of the table instead of materializing a
    masked gather over all maxp pages — 16x less HBM traffic."""
    all_axes = tuple(dp_axes) + (model_axis,)

    def local(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
              new_k: jax.Array, new_v: jax.Array, bt: jax.Array,
              lengths: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        # linear rank over (dp..., model); pool is laid out in the same
        # axis order so contiguous page ranges land per rank
        rank = 0
        for a in all_axes:
            rank = rank * lax.axis_size(a) + lax.axis_index(a)
        pp_local = k_pages.shape[0]
        base = rank * pp_local
        b, qh, hd = q.shape
        kvh = k_pages.shape[2]
        group = qh // kvh
        page = k_pages.shape[1]

        # -- append the new token's KV on the owning chip ---------------
        pidx = jnp.take_along_axis(
            bt, (lengths // page)[:, None].astype(jnp.int32), axis=1)[:, 0]
        poff = (lengths % page).astype(jnp.int32)
        lp = pidx - base
        own = (lp >= 0) & (lp < pp_local)
        lp_safe = jnp.clip(lp, 0, pp_local - 1)
        cur_k = k_pages[lp_safe, poff]
        cur_v = v_pages[lp_safe, poff]
        k_pages = k_pages.at[lp_safe, poff].set(
            jnp.where(own[:, None, None], new_k.astype(k_pages.dtype),
                      cur_k))
        v_pages = v_pages.at[lp_safe, poff].set(
            jnp.where(own[:, None, None], new_v.astype(v_pages.dtype),
                      cur_v))

        # -- partial attention over owned pages --------------------------
        qg = q.reshape(b, kvh, group, hd)
        if contiguous:
            midx = lax.axis_index(model_axis)
            maxp = bt.shape[1]
            # offset of this rank's pool slice within ITS batch rows: when
            # the batch is dp-sharded the dp part of `base` aligns with the
            # local rows; when replicated (B < dp, e.g. long_500k B=1) the
            # global base indexes the single shared sequence directly
            base_local = midx * pp_local if batch_sharded else base
            acc, m, l = _partial_paged_attention_sliced(
                qg, k_pages, v_pages, bt,
                (lengths + 1).astype(jnp.int32), base_page=base,
                base_local=base_local, maxp=maxp, scale=hd ** -0.5)
        else:
            acc, m, l = _partial_paged_attention(
                qg, k_pages, v_pages, bt, (lengths + 1).astype(jnp.int32),
                base_page=base, scale=hd ** -0.5)

        # -- one-round combine: online-softmax merge across the axes that
        # hold partials (model always; the dp axes too when the batch is
        # replicated and its pages are spread over dp) -------------------
        merge_axes = (model_axis,) if batch_sharded \
            else tuple(dp_axes) + (model_axis,)
        mg = m
        for ax in merge_axes:
            mg = lax.pmax(mg, ax)
        w = jnp.exp(m - mg)
        acc = lax.psum(acc * w[..., None], merge_axes)
        l = lax.psum(l * w, merge_axes)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, qh, hd).astype(q.dtype), k_pages, v_pages

    dp = tuple(dp_axes) if batch_sharded else None
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None),                # q (replicated/model)
                  P(all_axes, None, None, None),    # k_pages
                  P(all_axes, None, None, None),    # v_pages
                  P(dp, None, None),                # new_k (B, KVH, D)
                  P(dp, None, None),                # new_v
                  P(dp, None),                      # block tables
                  P(dp)),                           # lengths
        out_specs=(P(dp, None, None),
                   P(all_axes, None, None, None),
                   P(all_axes, None, None, None)),
        check_rep=False)
