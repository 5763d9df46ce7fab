"""Generic double-indirection gather — the Tiara ISA's Load-chain as a
BlockSpec.

``out[i] = pool[table[ids[i]]]``: both the request list and the
translation table ride in SMEM via scalar prefetch, and the HBM page each
grid step DMAs into VMEM is chosen by dereferencing *two* levels of
indirection inside the ``index_map`` — a 2-level page-table walk executed
by the memory system itself, one pass, no materialized intermediate.

Used for MoE expert-slab gather (expert id -> translation table -> slab)
and raw KV block fetch outside attention.  Rows are (row_words,) and the
pool is (n_rows, row_words).  The kernel views the pool as
(n_rows, 1, row_words) so that a one-row block spans the full last two
dimensions, which is what the TPU lowering asks of a block narrower than
(8, 128).  TPU kernels have no 64-bit types: a caller holding 64-bit
words bitcasts them to 32-bit pairs first (the gather moves bits, so the
result is exact), and a 64-bit pool is refused here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(ids_ref, table_ref, pool_ref, o_ref):
    del ids_ref, table_ref       # consumed by the index_map (the point!)
    o_ref[...] = pool_ref[...]


def _row(r):
    """Block index of row ``r`` in the (N, 1, R) view.  The zeros are
    built as int32 so the index map stays 32-bit when the caller traces
    with 64-bit mode on, as the VM's trace compiler does."""
    zero = jnp.zeros((), jnp.int32)
    return r, zero, zero


def tiara_gather_kernel(pool: jax.Array, table: jax.Array,
                        ids: jax.Array, *, interpret: bool = False
                        ) -> jax.Array:
    """pool (N, R); table (T,) int32: logical -> physical row;
    ids (n,) int32: requested logical rows.  Returns (n, R)."""
    n_rows, row_words = pool.shape
    (n_req,) = ids.shape
    if jnp.dtype(pool.dtype).itemsize > 4:
        raise ValueError(
            f"tiara_gather_kernel moves 32-bit words; got a {pool.dtype} "
            f"pool (bitcast 64-bit words to int32 pairs first)")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_req,),
        in_specs=[
            pl.BlockSpec((1, 1, row_words),
                         lambda i, ids_r, tbl_r: _row(tbl_r[ids_r[i]])),
        ],
        out_specs=pl.BlockSpec((1, 1, row_words),
                               lambda i, ids_r, tbl_r: _row(i)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_req, 1, row_words), pool.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), table.astype(jnp.int32),
      pool.reshape(n_rows, 1, row_words))
    return out.reshape(n_req, row_words)
