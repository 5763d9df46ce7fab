"""Dry-run cells: (arch x shape x mesh) -> step fn + arg specs + shardings.

``input_specs()`` returns ShapeDtypeStruct stand-ins for every model input
(weak-type-correct, shardable, zero allocation); ``make_cell()`` bundles
them with the jitted step function and its in/out shardings so the dry-run
is a pure ``.lower().compile()``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig, ShapeSpec, cell_applicable
from repro.models import transformer as tf
from repro.models.attention import PagedKV
from repro.models.blocks import BlockCache
from repro.models.mamba import MambaCache
from repro.models.rwkv import RWKVCache
from repro.launch.mesh import auto_view, dp_axes, dp_size
from repro.training.optimizer import AdamWConfig, AdamWState, warmup_cosine
from repro.training.train_step import TrainState, make_train_step




@dataclasses.dataclass
class Cell:
    cfg: ArchConfig
    shape: ShapeSpec
    fn: Callable                 # step function to jit
    args: Tuple                  # ShapeDtypeStruct pytrees
    in_specs: Tuple              # PartitionSpec pytrees
    out_specs: Any               # PartitionSpec pytree or None (=auto)
    donate: Tuple[int, ...] = ()
    notes: str = ""


def _b(dp, size_b: int, dpsz: int):
    """Batch-dim spec: shard over dp when it divides, else replicate."""
    return dp if size_b % dpsz == 0 and size_b >= dpsz else None


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _train_batch(cfg: ArchConfig, shape: ShapeSpec, dp, dpsz):
    b, s = shape.global_batch, shape.seq_len
    bspec = _b(dp, b, dpsz)
    args = {"tokens": _sds((b, s), jnp.int32),
            "labels": _sds((b, s), jnp.int32)}
    specs = {"tokens": P(bspec, None), "labels": P(bspec, None)}
    _add_modality(cfg, args, specs, b, s, bspec)
    return args, specs


def _add_modality(cfg, args, specs, b, s, bspec, *, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.rope == "mrope":
        args["positions3"] = _sds((3, b, s), jnp.int32)
        specs["positions3"] = P(None, bspec, None)
        args["embeds"] = _sds((b, s, cfg.d_model), dtype)
        specs["embeds"] = P(bspec, None, None)
    if cfg.enc_dec:
        args["enc_embeds"] = _sds((b, s, cfg.d_model), dtype)
        specs["enc_embeds"] = P(bspec, None, None)
        args["enc_lengths"] = _sds((b,), jnp.int32)
        specs["enc_lengths"] = P(bspec)


def _cache_structs_and_specs(cfg: ArchConfig, b: int, maxp: int, dp, dpsz,
                             cross_len: int = 0, pool_all_axes=None):
    structs = jax.eval_shape(
        lambda: tf.init_caches(cfg, b, maxp,
                               cross_len=cross_len))
    bspec = _b(dp, b, dpsz)
    pool_spec = dp          # pool = b * maxp, page-granular "memory pool"
    specs = []
    for spec_el, struct_el in zip(cfg.pattern, structs):
        paged = mamba = rwkv = cross_k = cross_v = None
        if struct_el.paged is not None:
            if pool_all_axes is not None:
                # one-round variant: whole pages fully distributed over
                # every mesh axis; they never cross the wire
                pg = P(None, pool_all_axes, None, None, None)
            else:
                # baseline: pool over the dp bundle (pages are batch-
                # owned); the *page* (token-slot) dim over model — it
                # divides for every arch, unlike kv heads (8 or 4 < 16)
                pg = P(None, pool_spec, "model", None, None)
            paged = PagedKV(pg, pg)
        if struct_el.mamba is not None:
            mamba = MambaCache(h=P(None, bspec, "model", None),
                               conv=P(None, bspec, None, "model"))
        if struct_el.rwkv is not None:
            rwkv = RWKVCache(state=P(None, bspec, "model", None, None),
                             x_time=P(None, bspec, None),
                             x_chan=P(None, bspec, None))
        if struct_el.cross_k is not None:
            cross_k = P(None, bspec, None, "model", None)
            cross_v = P(None, bspec, None, "model", None)
        specs.append(BlockCache(paged=paged, mamba=mamba, rwkv=rwkv,
                                cross_k=cross_k, cross_v=cross_v))
    return structs, tuple(specs)


def make_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, *,
              state_bits: int = 32, variant: str = "baseline"
              ) -> Optional[Cell]:
    """The cell's step, args and shardings, built on the ``Auto`` view
    of ``mesh`` (:func:`repro.launch.mesh.auto_view`).  A mesh the caller
    built with ``Explicit`` axes (``jax.make_mesh``'s default) gets a
    step wrapped in ``auto_axes``, so it runs under that mesh too."""
    explicit = tuple(a for a, t in zip(mesh.axis_names, mesh.axis_types)
                     if t == AxisType.Explicit)
    cell = _make_cell(cfg, shape, auto_view(mesh), state_bits, variant)
    if cell is not None and explicit:
        cell.fn = jax.sharding.auto_axes(cell.fn, axes=explicit,
                                         out_sharding=cell.out_specs)
    return cell


def _make_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, state_bits: int,
               variant: str) -> Optional[Cell]:
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return None
    dp = dp_axes(mesh)
    dpsz = dp_size(mesh)
    b, s = shape.global_batch, shape.seq_len
    bspec = _b(dp, b, dpsz)
    cfg = cfg.replace(attn_impl="xla",
                      dp_spec=tuple(bspec) if bspec else None)
    pool_all_axes = None
    if variant in ("tiara_decode", "tiara_decode_v2") \
            and shape.kind == "decode":
        from repro.distributed.paged_decode import sharded_paged_attention
        cfg = cfg.replace(
            paged_attn_fn=sharded_paged_attention(
                mesh, dp, "model",
                contiguous=(variant == "tiara_decode_v2"),
                batch_sharded=bspec is not None))
        pool_all_axes = tuple(dp) + ("model",)
    elif variant == "remat_layer":
        cfg = cfg.replace(remat_unit="layer")
    elif variant == "moe_hints":
        cfg = cfg.replace(moe_hints=True)
    elif variant == "remat_layer+moe_hints":
        cfg = cfg.replace(remat_unit="layer", moe_hints=True)
    elif variant in ("moe_ep", "moe_ep+remat_layer"):
        from repro.distributed.moe_ep import make_moe_ep
        moe_specs = {sp.moe for sp in cfg.pattern if sp.moe is not None}
        assert len(moe_specs) == 1, "one MoE spec per arch"
        cfg = cfg.replace(
            moe_fn=make_moe_ep(mesh, dp, next(iter(moe_specs))),
            remat_unit="layer" if "remat" in variant else cfg.remat_unit)
    pspecs = tf.param_specs(cfg)

    if shape.kind == "train":
        opt_cfg = AdamWConfig(lr=warmup_cosine(3e-4, 100, 10_000),
                              state_bits=state_bits)
        init_state, train_step = make_train_step(cfg, opt_cfg)
        state_struct = jax.eval_shape(init_state, jax.random.PRNGKey(0))
        if state_bits == 8:
            def q8spec(ps):
                from repro.training.optimizer import Q8
                # scales mirror the codes' leading-dim sharding (the last
                # dim collapses into per-block scales)
                return jax.tree_util.tree_map(
                    lambda sp: Q8(codes=sp,
                                  scales=P(*sp[:-1], None)
                                  if len(sp) else P()),
                    ps, is_leaf=lambda x: isinstance(x, P))
            mu_spec = q8spec(pspecs)
        else:
            mu_spec = pspecs
        state_spec = TrainState(step=P(), params=pspecs,
                                opt=AdamWState(count=P(), mu=mu_spec,
                                               nu=mu_spec))
        batch_struct, batch_spec = _train_batch(cfg, shape, dp, dpsz)
        metrics_spec = {k: P() for k in
                        ("nll", "aux", "loss", "grad_norm", "lr")}
        return Cell(cfg=cfg, shape=shape, fn=train_step,
                    args=(state_struct, batch_struct),
                    in_specs=(state_spec, batch_spec),
                    out_specs=(state_spec, metrics_spec),
                    donate=(0,))

    # serving shapes
    param_struct = tf.param_shapes(cfg)
    maxp = s // cfg.page_size + (1 if shape.kind == "decode" else 0)
    maxp = (maxp + 63) // 64 * 64      # pool divisibility on the dp bundle
    if pool_all_axes is not None:
        # pool (= b * maxp) must divide the full chip count for the
        # fully-distributed page layout
        import math
        chips = dpsz * mesh.shape["model"]
        need = chips // math.gcd(b, chips)
        maxp = (maxp + need - 1) // need * need
    cross_len = s if cfg.enc_dec else 0
    cache_structs, cache_specs = _cache_structs_and_specs(
        cfg, b, maxp, dp, dpsz, cross_len=cross_len,
        pool_all_axes=pool_all_axes)

    if shape.kind == "prefill":
        batch = {"tokens": _sds((b, s), jnp.int32),
                 "block_tables": _sds((b, maxp), jnp.int32),
                 "lengths": _sds((b,), jnp.int32)}
        bsp = {"tokens": P(bspec, None), "block_tables": P(bspec, None),
               "lengths": P(bspec)}
        _add_modality(cfg, batch, bsp, b, s, bspec)

        def prefill_step(params, caches, batch):
            out = tf.apply_model(params, cfg, {**batch, "caches": caches},
                                 mode="prefill")
            idx = jnp.maximum(batch["lengths"] - 1, 0)
            last = jnp.take_along_axis(
                out.logits, idx[:, None, None], axis=1)[:, 0]
            return last, out.caches

        out_specs = (P(bspec, "model"), cache_specs)
        return Cell(cfg=cfg, shape=shape, fn=prefill_step,
                    args=(param_struct, cache_structs, batch),
                    in_specs=(pspecs, cache_specs, bsp),
                    out_specs=out_specs, donate=(1,))

    # decode: one new token against a seq_len-token cache
    batch = {"tokens": _sds((b, 1), jnp.int32),
             "block_tables": _sds((b, maxp), jnp.int32),
             "lengths": _sds((b,), jnp.int32)}
    bsp = {"tokens": P(bspec, None), "block_tables": P(bspec, None),
           "lengths": P(bspec)}
    if cfg.rope == "mrope":
        batch["positions3"] = _sds((3, b, 1), jnp.int32)
        bsp["positions3"] = P(None, bspec, None)
    if cfg.enc_dec:
        batch["enc_lengths"] = _sds((b,), jnp.int32)
        bsp["enc_lengths"] = P(bspec)

    def decode_step(params, caches, batch):
        out = tf.apply_model(params, cfg, {**batch, "caches": caches},
                             mode="decode")
        return out.logits[:, 0], out.caches

    out_specs = (P(bspec, "model"), cache_specs)
    return Cell(cfg=cfg, shape=shape, fn=decode_step,
                args=(param_struct, cache_structs, batch),
                in_specs=(pspecs, cache_specs, bsp),
                out_specs=out_specs, donate=(1,))


def lower_cell(cell: Cell, mesh):
    """jit with explicit shardings and lower — no allocation, no compile.

    The mesh is made ambient so bare-PartitionSpec activation hints inside
    the model (transformer._hint) resolve."""
    def to_sharding(spec_tree_):
        return jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), spec_tree_,
            is_leaf=lambda x: isinstance(x, P))

    in_sh = to_sharding(cell.in_specs)
    out_sh = to_sharding(cell.out_specs) if cell.out_specs is not None \
        else None
    jitted = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=cell.donate)
    with jax.set_mesh(mesh):
        return jitted.lower(*cell.args)
