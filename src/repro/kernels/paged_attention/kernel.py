"""Paged-attention decode kernel — Tiara's register-chained load on TPU.

The Indirection Wall on a TPU: decode attention must read KV data whose
HBM location is known only through the Block Table.  A host-driven design
gathers pages with XLA ops (extra HBM round trips and a materialized
contiguous copy).  Here the *loaded value is the next address*: the block
table rides in SMEM via scalar prefetch, and each grid step's BlockSpec
``index_map`` dereferences it to choose which HBM page the next DMA brings
into VMEM — the exact analogue of a Tiara MP chaining ``Load``s, with the
async-copy/compute overlap playing the paper's ``async Memcpy + Wait``.

Layout:
  q            (B, KVH, G, D)     one new token per sequence, grouped GQA
  k/v_pages    (P, page, KVH, D)  the paged KV pool
  block_tables (B, maxp) int32    logical page i of seq b -> physical page
  lengths      (B,) int32         tokens currently in each sequence
  out          (B, KVH, G, D)

The kernel views each pool as (P, page, KVH * D) — a free reshape of the
contiguous pool — so one grid step DMAs a whole page, every KV head at
once, and slices each head's D lanes out of the page.  A block of one head,
(1, page, 1, D), would be the natural spelling, but the TPU lowering
needs the last two block dimensions to be (8k, 128m) or the full array
dimensions, and one of KVH heads is neither.

Grid: (B, maxp), pages innermost; flash-style running softmax per KV
head in VMEM scratch.  Pages past a sequence's length still prefetch
(the table pads with page 0) but their compute is skipped with
``pl.when`` — the standard dummy-fetch idiom for data-dependent grids.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(lengths_ref, tables_ref,      # scalar prefetch (SMEM)
                       q_ref, k_ref, v_ref,          # VMEM blocks
                       o_ref,                        # VMEM output block
                       m_scr, l_scr, acc_scr,        # VMEM scratch
                       *, page_size: int, n_kv_heads: int, head_dim: int,
                       scale: float):
    b = pl.program_id(0)
    i = pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(i * page_size < length)
    def _compute():
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        for h in range(n_kv_heads):
            lanes = pl.ds(h * head_dim, head_dim)
            q = q_ref[0, h].astype(jnp.float32) * scale           # (G, D)
            k = k_ref[0, :, lanes].astype(jnp.float32)            # (page, D)
            v = v_ref[0, :, lanes].astype(jnp.float32)            # (page, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(pos < length, s, NEG_INF)               # (G, page)
            m_prev = m_scr[h]                                     # (G, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_cur

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """Raw pallas_call wrapper; use repro.kernels.paged_attention.ops for
    the jitted public entry point."""
    batch, kvh, group, head_dim = q.shape
    n_pages, page_size, kvh_p, head_dim_p = k_pages.shape
    assert (kvh_p, head_dim_p) == (kvh, head_dim), "KV layout mismatch"
    assert v_pages.shape == k_pages.shape
    b_t, max_pages = block_tables.shape
    assert b_t == batch and lengths.shape == (batch,)
    if scale is None:
        scale = head_dim ** -0.5

    kernel = functools.partial(_paged_attn_kernel, page_size=page_size,
                               n_kv_heads=kvh, head_dim=head_dim,
                               scale=float(scale))
    page_words = kvh * head_dim
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, max_pages),
        in_specs=[
            pl.BlockSpec((1, kvh, group, head_dim),
                         lambda b, i, ln, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, page_words),
                         lambda b, i, ln, bt: (bt[b, i], 0, 0)),
            pl.BlockSpec((1, page_size, page_words),
                         lambda b, i, ln, bt: (bt[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvh, group, head_dim),
                               lambda b, i, ln, bt: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, group, 1), jnp.float32),
            pltpu.VMEM((kvh, group, 1), jnp.float32),
            pltpu.VMEM((kvh, group, head_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), q,
      k_pages.reshape(n_pages, page_size, page_words),
      v_pages.reshape(n_pages, page_size, page_words))
