"""Registration-time trace compiler — the interpreter-free fast path.

The facts the static verifier already proves about an operator (jumps are
forward-only, every loop has a static trip-count cap, nesting is bounded)
are exactly what a trace compiler needs: the whole program can be lowered
at registration time to **predicated dataflow** — a chain of
``jnp.take``-style gathers and deterministic scatters with no interpreter
dispatch at all.  This is the software analogue of the paper's point that
hot remote-memory paths (pointer chase, page-table walk, KV block fetch)
should run as *superoperators* baked into the fabric, not as general
interpreted programs.

Lowering rules (B = request batch, every value is an int64 ``(B,)`` lane):

  * a loop lowers to a ``lax.while_loop`` whose body is the predicated
    trace of one iteration; iteration ``j`` runs under the predicate
    ``j < min(trip_reg, cap)``, and the loop stops at the static cap or
    at the first trip with no live lane (such a trip would change
    nothing).  Each trip's registers and halt mask are materialized in
    the loop carry: fully unrolled, XLA fuses a pointer chase's hops
    into one kernel, and because the protection path makes a hop's
    address and its halt mask depend on each other, XLA:CPU's fusion
    emitter re-derives every earlier hop once per use — run time
    exponential in the trip count.  A loop body is traced once, so the
    trace grows with the program's length (at most the 1024-entry
    instruction store), not with its step bound: every verified operator
    compiles;
  * forward jumps become predicate splits: the fall-through side continues
    under ``pred & ~take`` and the taken lanes re-join at the target pc
    (a jump that escapes loop bodies masks the remaining iterations —
    the Fig. 5 distributed-lock "break");
  * RET latches ``ret``/``status`` under the live predicate and removes
    the lane from every later instruction;
  * LOAD/STORE lower to gathers / deterministic scatters, CAS/CAA to a
    serialized ``lax.scan`` over the batch (atomics keep pyvm
    request-order), MEMCPY to a window gather plus a deterministic
    last-writer-wins scatter in round-robin commit order;
  * the canonical *gather-chain* loop (``load id; load translation;
    memcpy row``) — MoE expert gather and paged-KV block fetch — is
    recognized structurally and fused into one two-level batched gather,
    optionally routed through the ``kernels/tiara_gather`` Pallas kernel.

Exactness: at batch=1 the compiled operator is bit-identical to the
``pyvm`` oracle (memory, ret, status, steps, registers).  For batches the
semantics are the engine's round-robin interleaving; like the batched
interpreter's vectorized step it assumes no request *reads* a word another
request *writes at the same trace position* (atomics excepted — they are
fully serialized).  Contended workloads belong on the batched interpreter,
which detects conflicts per step and falls back to exact serialization.

The in-flight async counter is not modeled: WAIT only clamps a counter
that never feeds a value (copies apply functionally at issue; timing is
the simulator's job), so the compiled path drops it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import costmodel as _costmodel
from repro.core import isa
from repro.core.isa import (Alu, Instr, Op, FLAG_ASYNC, FLAG_DEV_REG,
                            FLAG_DSTDEV_REG, FLAG_IMMB, FLAG_LEN_REG,
                            FLAG_MREG, FLAG_SRCDEV_REG, DEV_LOCAL, ERR_REG)
from repro.core.memory import RegionTable
from repro.core.verifier import LoopInfo, VerifiedOperator
from repro.core import vm as _vm

_REG_MASK = isa.NUM_REGS - 1

# fault-site device column sentinels (see _Tracer.sites / _finalize_fault):
# >= 0 is a static device id, _DEV_HOME resolves to the lane's home at
# finalization, _DEV_LATCHED reads the runtime f_dev plane
_DEV_HOME = -1
_DEV_LATCHED = -2

# the _Tracer state a loop trip carries besides the registers
_CARRIED = ("memf", "halted", "ret", "status", "steps",
            "f_site", "f_addr", "f_dev", "f_aux")

# Iterations per double-buffered gather-chain chunk: small enough that
# chunk k+1's row gather overlaps a meaningful fraction of chunk k's
# scatter, large enough that per-chunk scatter setup amortizes.  Single
# source of truth is the cost model's ``dbuf_chunk_iters`` — the auto
# dispatch prices chunk counts and the overlap-eligibility threshold
# with it, so the engine must chunk identically.
DBUF_CHUNK = _costmodel.EngineCost().dbuf_chunk_iters


class CompileError(Exception):
    pass



# ---------------------------------------------------------------------------
# Shared lowering helpers (also used by the distributed layer)
# ---------------------------------------------------------------------------

def masked_row_gather(pool: jnp.ndarray, idx: jnp.ndarray,
                      live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``out[...] = pool[idx[...]]`` with rows outside ``[0, len(pool))``
    (or with ``live == False``) replaced by zeros — the memory-side half of
    the compiled gather chain, shared with ``distributed/tiara_fetch`` and
    ``distributed/paged_decode``."""
    n = pool.shape[0]
    ok = (idx >= 0) & (idx < n)
    if live is not None:
        ok = ok & live
    rows = pool[jnp.clip(idx, 0, n - 1)]
    shape = ok.shape + (1,) * (rows.ndim - ok.ndim)
    return jnp.where(ok.reshape(shape), rows, jnp.zeros((), rows.dtype))


def det_scatter(mem_flat: jnp.ndarray, idx: jnp.ndarray, val: jnp.ndarray,
                live: jnp.ndarray) -> jnp.ndarray:
    """Deterministic scatter: among duplicate targets the **last live lane
    in flat order wins** — flat order is the engine's round-robin commit
    order.  Dead lanes are routed out of bounds and dropped."""
    size = mem_flat.shape[0]
    f = jnp.where(live, idx, size).reshape(-1)
    v = val.reshape(-1)
    m = f.shape[0]
    # stable grouping: sort by (target, lane); the last element of each
    # run of equal targets is the winner
    comp = f * m + jnp.arange(m, dtype=f.dtype)
    order = jnp.argsort(comp)
    fs = f[order]
    last = jnp.concatenate([fs[1:] != fs[:-1],
                            jnp.ones((1,), dtype=bool)])
    tgt = jnp.where(last, fs, size)
    return mem_flat.at[tgt].set(v[order], mode="drop")


def _alu_static(aop: int, a, b):
    """ALU with a *static* opcode — the compiled trace emits only the one
    operation the instruction names (no 16-way select)."""
    if aop == Alu.ADD:
        return a + b
    if aop == Alu.SUB:
        return a - b
    if aop == Alu.MUL:
        return a * b
    if aop == Alu.AND:
        return a & b
    if aop == Alu.OR:
        return a | b
    if aop == Alu.XOR:
        return a ^ b
    if aop == Alu.SHL:
        return a << (b & 63)
    if aop == Alu.SHR:
        return lax.shift_right_logical(a, b & 63)
    if aop == Alu.EQ:
        return (a == b).astype(jnp.int64)
    if aop == Alu.NE:
        return (a != b).astype(jnp.int64)
    if aop == Alu.LT:
        return (a < b).astype(jnp.int64)
    if aop == Alu.GE:
        return (a >= b).astype(jnp.int64)
    if aop == Alu.MIN:
        return jnp.minimum(a, b)
    if aop == Alu.MAX:
        return jnp.maximum(a, b)
    raise CompileError(f"bad ALU op {aop}")


# ---------------------------------------------------------------------------
# Mixed-batch planner
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    """One same-op_id run of the stable-sorted batch: requests at sorted
    positions ``[start, end)`` all dispatch to ``op_id``.  In a
    home-bucketed plan the run is additionally same-``home`` — the unit
    of placement on the device mesh (the whole segment executes on
    device ``home``)."""

    op_id: int
    start: int
    end: int
    home: int = 0

    @property
    def size(self) -> int:
        return self.end - self.start


@dataclasses.dataclass(frozen=True)
class MixedPlan:
    """The compiled path's plan for a mixed-op batch.

    A compiled trace executes one program, so a mixed batch
    is *segmented*: requests are stable-sorted by op_id (preserving
    arrival order within an op — the ordering atomics serialize by), each
    contiguous segment runs through its own compiled trace against the
    shared pool, and per-request outputs scatter back to arrival order
    through ``inverse``.  Planning is pure bookkeeping — O(B log B) once
    per wave — and is exactly the batching a NIC dispatcher would do when
    filling per-MP task queues from a mixed arrival stream.

    **Home-bucketed (sharded) plans**: built with ``homes=`` +
    ``n_devices=``, the stable sort key becomes ``(home, op_id)`` —
    device-major, so device ``d``'s sub-wave is the contiguous slice of
    the sorted batch holding exactly the requests whose ``home`` it
    owns, itself sorted into same-op segments (segments stay the unit of
    placement; each carries its ``home``).  ``device_counts[d]`` is the
    sub-wave's size, ``batch_per_device`` the padded lane count the
    sharded engine runs, and the *same* arrival-order ``inverse``
    permutation still does the reply scatter.
    """

    op_ids: np.ndarray            # i64 [B] arrival-order op ids
    order: np.ndarray             # i64 [B]: sorted position -> arrival idx
    inverse: np.ndarray           # i64 [B]: arrival idx -> sorted position
    segments: Tuple[Segment, ...]
    homes: Optional[np.ndarray] = None   # i64 [B] arrival-order homes
    n_devices: int = 1
    device_counts: Optional[np.ndarray] = None   # i64 [n_devices]

    @property
    def batch(self) -> int:
        return int(self.op_ids.shape[0])

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def sharded(self) -> bool:
        return self.device_counts is not None

    @property
    def batch_per_device(self) -> int:
        """Per-device lane count of the sharded engine: every ragged
        sub-wave padded to the largest one (>= 1 so empty devices still
        hold a halted pad lane)."""
        if self.device_counts is None:
            return self.batch
        return max(int(self.device_counts.max(initial=0)), 1)

    def segment_indices(self, seg: Segment) -> np.ndarray:
        """Arrival indices of the requests in ``seg`` (arrival order)."""
        return self.order[seg.start:seg.end]

    def device_segments(self, device: int) -> Tuple[Segment, ...]:
        """The placement units assigned to ``device`` (sharded plans)."""
        return tuple(s for s in self.segments if s.home == device)


def plan_mixed_batch(op_ids, homes=None,
                     n_devices: Optional[int] = None) -> MixedPlan:
    """Stable-sort a batch's op_ids and segment it into same-op runs.

    With ``homes=`` and ``n_devices=``, additionally bucket the segments
    by ``home`` into per-device sub-waves (sort key ``(home, op_id)``,
    arrival-stable) — the placement plan the sharded engine executes.
    """
    ids = np.asarray(list(op_ids), dtype=np.int64)
    if ids.ndim != 1 or ids.size == 0:
        raise ValueError("op_ids must be a non-empty 1-D sequence")
    if homes is None:
        order = np.argsort(ids, kind="stable").astype(np.int64)
        hsort = None
        device_counts = None
        n_dev = 1
    else:
        if n_devices is None:
            raise ValueError("home-bucketed plans need n_devices=")
        n_dev = int(n_devices)
        h = np.asarray(list(homes), dtype=np.int64)
        if h.shape != ids.shape:
            raise ValueError(
                f"homes shape {h.shape} does not match op_ids {ids.shape}")
        if ids.size and (h.min() < 0 or h.max() >= n_dev):
            raise ValueError(
                f"homes must lie in [0, {n_dev}); got range "
                f"[{h.min()}, {h.max()}]")
        # np.lexsort: last key is primary; stable, so arrival order is
        # preserved within each (home, op) bucket
        order = np.lexsort((ids, h)).astype(np.int64)
        hsort = h[order]
        device_counts = np.bincount(h, minlength=n_dev).astype(np.int64)
    inverse = np.empty_like(order)
    inverse[order] = np.arange(ids.size, dtype=np.int64)
    sorted_ids = ids[order]
    brk = sorted_ids[1:] != sorted_ids[:-1]
    if hsort is not None:
        brk = brk | (hsort[1:] != hsort[:-1])
    starts = np.flatnonzero(np.concatenate([[True], brk]))
    bounds = list(starts) + [ids.size]
    segments = tuple(
        Segment(op_id=int(sorted_ids[s]), start=int(s), end=int(e),
                home=int(hsort[s]) if hsort is not None else 0)
        for s, e in zip(bounds[:-1], bounds[1:]))
    return MixedPlan(op_ids=ids, order=order, inverse=inverse,
                     segments=segments,
                     homes=None if homes is None else h,
                     n_devices=n_dev, device_counts=device_counts)


# ---------------------------------------------------------------------------
# Gather-chain superoperator detection
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GatherChain:
    """The canonical indirection loop (paper §4.5/§4.6):

        loop (n, cap):
            load  id    <- ids_region[i]
            load  paddr <- table_region[id]
            memcpy dst_region[dst] <- pool_region[paddr] x W   (async ok)
            dst += W
            i   += 1
    """

    loop_pc: int
    cap: int
    ids_rid: int
    table_rid: int
    pool_rid: int
    dst_rid: int
    row_words: int
    i_reg: int
    id_reg: int
    paddr_reg: int
    dst_reg: int
    is_async: bool


def _plain_local_load(ins: Instr) -> bool:
    return (ins.op == Op.LOAD and ins.imm == 0 and ins.flags == 0
            and ins.e == DEV_LOCAL)


def _is_add_imm(ins: Instr, reg: int, imm: Optional[int] = None) -> bool:
    """``reg += imm`` (immediate ADD updating ``reg`` in place)."""
    return (ins.op == Op.ALU and ins.d == int(Alu.ADD)
            and bool(ins.flags & FLAG_IMMB) and ins.dst == ins.a == reg
            and (imm is None or ins.imm == imm))


def match_gather_chain_ex(instrs: List[Instr], loop: LoopInfo
                          ) -> Tuple[Optional[GatherChain], Optional[str]]:
    """Structural match of the loop body against the gather-chain shape.
    Purely static — checked once at compile time.  Returns
    ``(chain, None)`` on a match and ``(None, reason)`` on a near-miss,
    where ``reason`` is the *first* structural check that failed — the
    registry surfaces it so a silently-slow almost-chain is explainable.
    """
    body = instrs[loop.start:loop.end + 1]
    if len(body) != 5:
        return None, (f"body has {len(body)} instructions, not the "
                      f"5-instruction chain shape")
    ld_id, ld_tr, mc, add_dst, add_i = body
    lp = instrs[loop.pc]

    if not _plain_local_load(ld_id):
        return None, "body[0] is not a plain local load (imm 0, no flags)"
    if not _plain_local_load(ld_tr):
        return None, "body[1] is not a plain local load (imm 0, no flags)"
    if ld_tr.b != ld_id.dst:                     # chained: id -> translation
        return None, ("body[1] offset register is not body[0]'s "
                      "destination (loads are not chained)")
    if mc.op != Op.MEMCPY:
        return None, "body[2] is not a MEMCPY"
    if mc.flags & (FLAG_LEN_REG | FLAG_DSTDEV_REG | FLAG_SRCDEV_REG):
        return None, "MEMCPY uses a dynamic length or device register"
    if mc.dst != DEV_LOCAL or mc.c != DEV_LOCAL:
        return None, "MEMCPY is not local-to-local"
    if mc.e != ld_tr.dst:                        # src offset = translation
        return None, ("MEMCPY source offset is not the translation "
                      "load's destination")
    w = int(mc.imm)
    if not (0 < w <= isa.MAX_MEMCPY_WORDS):
        return None, f"MEMCPY row width {w} outside (0, MAX_MEMCPY_WORDS]"
    if not _is_add_imm(add_dst, mc.b, w):
        return None, (f"body[3] is not 'dst += {w}' (immediate ADD of the "
                      f"row width)")
    if not _is_add_imm(add_i, ld_id.b, 1):
        return None, "body[4] is not 'i += 1' (immediate ADD of 1)"
    # distinct registers so the fused updates don't alias
    regs = (ld_id.b, ld_id.dst, ld_tr.dst, mc.b)
    if len(set(regs)) != 4:
        return None, "index/id/translation/dst registers are not distinct"
    return GatherChain(
        loop_pc=loop.pc, cap=int(lp.imm), ids_rid=ld_id.a,
        table_rid=ld_tr.a, pool_rid=mc.d, dst_rid=mc.a, row_words=w,
        i_reg=ld_id.b, id_reg=ld_id.dst, paddr_reg=ld_tr.dst,
        dst_reg=mc.b, is_async=bool(mc.flags & FLAG_ASYNC)), None


def match_gather_chain(instrs: List[Instr], loop: LoopInfo
                       ) -> Optional[GatherChain]:
    """Reason-free wrapper of :func:`match_gather_chain_ex` (hot path)."""
    return match_gather_chain_ex(instrs, loop)[0]


def find_gather_chains(op: VerifiedOperator) -> List[GatherChain]:
    """All gather-chain superoperators in a verified program (diagnostic /
    registry-level introspection)."""
    instrs = isa.decode_program(op.code)
    out = []
    for l in op.loops:
        g = match_gather_chain(instrs, l)
        if g is not None:
            out.append(g)
    return out


# ---------------------------------------------------------------------------
# Wider superoperator shapes (footprint-era matchers)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScatterReduce:
    """Conditional scatter-accumulate loop — the CAA analogue of the
    gather chain:

        loop (n, cap):
            load v   <- src_region[i]
            caa  old <- acc_region[j] ?= cmp, += v
            j += stride
            i += 1

    Fused to one snapshot gather + elementwise compare + scatter-add.
    The fusion is **only exact when every CAA address in the wave is
    touched at most once** — within a lane that is the static
    ``|stride| * cap <= region size`` check, across lanes it is the
    registration-time conflict proof — so the tracer emits it only in
    ``noconflict`` builds (see :func:`build_compiled`)."""

    loop_pc: int
    cap: int
    src_rid: int
    acc_rid: int
    stride: int
    i_reg: int
    v_reg: int
    j_reg: int
    old_reg: int
    cmp_reg: int


def match_scatter_reduce(instrs: List[Instr], loop: LoopInfo
                         ) -> Optional[ScatterReduce]:
    """Structural match of the loop body against the scatter-reduce
    shape (purely static)."""
    body = instrs[loop.start:loop.end + 1]
    if len(body) != 4:
        return None
    ld, caa, add_j, add_i = body
    lp = instrs[loop.pc]
    if not _plain_local_load(ld):
        return None
    if caa.op != Op.CAA or caa.flags != 0 or caa.imm != 0 \
            or caa.e != DEV_LOCAL:
        return None
    if caa.d != ld.dst:                          # added value = loaded value
        return None
    if not (_is_add_imm(add_j, caa.b) and add_j.imm != 0):
        return None
    if not _is_add_imm(add_i, ld.b, 1):
        return None
    if caa.a == ld.a:          # src window must not alias the acc window
        return None
    regs = (ld.b, ld.dst, caa.b, caa.dst)
    if len(set(regs)) != 4 or caa.c in regs:
        return None
    return ScatterReduce(
        loop_pc=loop.pc, cap=int(lp.imm), src_rid=ld.a, acc_rid=caa.a,
        stride=int(add_j.imm), i_reg=ld.b, v_reg=ld.dst, j_reg=caa.b,
        old_reg=caa.dst, cmp_reg=caa.c)


@dataclasses.dataclass(frozen=True)
class MapLoop:
    """Elementwise map / zip-with over region windows:

        loop (n, cap):                 loop (n, cap):
            load a <- src[i]               load a <- src1[i]
            c = a OP rhs                   load b <- src2[i]
            store c -> dst[j]              c = a OP b
            j += 1                         store c -> dst[j]
            i += 1                         j += 1
                                           i += 1

    ``rhs`` in the unary form is an immediate or a loop-invariant
    register.  Fused to one window gather (two for zip), one
    elementwise ALU, and one deterministic scatter in (iteration,
    request) commit order.  ``src2_rid``/``b_reg`` are -1 and
    ``alu_imm`` carries the immediate for the unary form."""

    loop_pc: int
    cap: int
    src_rid: int
    src2_rid: int
    dst_rid: int
    alu_op: int
    alu_imm: Optional[int]
    rhs_reg: int               # invariant-register rhs for unary map, or -1
    i_reg: int
    j_reg: int
    a_reg: int
    b_reg: int
    c_reg: int
    is_zip: bool


def match_map_loop(instrs: List[Instr], loop: LoopInfo
                   ) -> Optional[MapLoop]:
    """Structural match of the loop body against the map / zip-with
    shapes (purely static)."""
    body = instrs[loop.start:loop.end + 1]
    lp = instrs[loop.pc]
    if len(body) == 5:
        ld_a, alu, st, add_j, add_i = body
        ld_b = None
    elif len(body) == 6:
        ld_a, ld_b, alu, st, add_j, add_i = body
    else:
        return None
    if not _plain_local_load(ld_a):
        return None
    if ld_b is not None:
        if not _plain_local_load(ld_b) or ld_b.b != ld_a.b \
                or ld_b.a == ld_a.a:
            return None
    if alu.op != Op.ALU or alu.d == int(Alu.ALWAYS):
        return None
    if alu.a != ld_a.dst:
        return None
    alu_imm: Optional[int] = None
    rhs_reg = -1
    if ld_b is not None:
        if (alu.flags & FLAG_IMMB) or alu.b != ld_b.dst:
            return None
    elif alu.flags & FLAG_IMMB:
        alu_imm = int(alu.imm)
    else:
        rhs_reg = alu.b
    if st.op != Op.STORE or st.imm != 0 or st.flags != 0 \
            or st.e != DEV_LOCAL:
        return None
    if st.dst != alu.dst:                        # stored value = ALU result
        return None
    if not _is_add_imm(add_j, st.b, 1):
        return None
    if not _is_add_imm(add_i, ld_a.b, 1):
        return None
    # dst window must not alias any src window (the fused gathers read a
    # pre-loop snapshot; distinct regions never alias)
    if st.a == ld_a.a or (ld_b is not None and st.a == ld_b.a):
        return None
    regs = [ld_a.b, st.b, ld_a.dst, alu.dst]
    if ld_b is not None:
        regs.append(ld_b.dst)
    if len(set(regs)) != len(regs):
        return None
    if rhs_reg >= 0 and rhs_reg in regs:         # rhs must be loop-invariant
        return None
    return MapLoop(
        loop_pc=loop.pc, cap=int(lp.imm), src_rid=ld_a.a,
        src2_rid=ld_b.a if ld_b is not None else -1, dst_rid=st.a,
        alu_op=int(alu.d), alu_imm=alu_imm, rhs_reg=rhs_reg,
        i_reg=ld_a.b, j_reg=st.b, a_reg=ld_a.dst,
        b_reg=ld_b.dst if ld_b is not None else -1, c_reg=alu.dst,
        is_zip=ld_b is not None)


def superop_report(op: VerifiedOperator) -> Dict[str, object]:
    """Which superoperators each loop of ``op`` matches, plus — when a
    loop matches nothing — the first structural reason the gather-chain
    matcher bailed (registry introspection; see ``registry.dump()``)."""
    instrs = isa.decode_program(op.code)
    matched: List[Tuple[str, int]] = []
    near_miss: Optional[str] = None
    for l in op.loops:
        g, reason = match_gather_chain_ex(instrs, l)
        if g is not None:
            matched.append(("gather_chain", l.pc))
            continue
        sr = match_scatter_reduce(instrs, l)
        if sr is not None:
            matched.append(("scatter_reduce", l.pc))
            continue
        ml = match_map_loop(instrs, l)
        if ml is not None:
            matched.append(("zip_loop" if ml.is_zip else "map_loop", l.pc))
            continue
        if near_miss is None:
            near_miss = f"pc {l.pc}: {reason}"
    return {"matched": matched, "near_miss": near_miss}


# ---------------------------------------------------------------------------
# The trace emitter
# ---------------------------------------------------------------------------

class _Tracer:
    """Emits the predicated trace of one verified operator.

    Mutable traced state: the 16 register lanes, the flattened shared
    memory pool, the halt/ret/status/step accumulators and the fault
    record (:data:`_CARRIED` — what a loop trip carries).  Branches exist
    only at Python time; the one control flow in the lowered program is
    the ``while_loop`` of each generic loop.
    """

    def __init__(self, *, instrs, loops, base, mask, n_dev, pool_words,
                 batch, homes, failed, mem_flat, regs, impl, superops,
                 double_buffer=False, protect=True, check_failed=True,
                 noconflict=False):
        self.instrs = instrs
        self.loops = loops                  # pc -> LoopInfo
        self.base = base                    # static np arrays
        self.mask = mask
        self.n_dev = n_dev
        self.P = pool_words
        self.B = batch
        self.homes = homes                  # (B,) traced
        self.failed = failed                # (n_dev,) traced
        self.memf = mem_flat                # (n_dev * P,) traced
        self.regs = regs                    # list of 16 (B,) traced lanes
        self.impl = impl
        self.superops = superops
        self.double_buffer = double_buffer
        self.protect = protect
        self.check_failed = check_failed
        self.noconflict = noconflict
        zero = jnp.zeros(batch, jnp.int64)
        self.halted = jnp.zeros(batch, bool)
        self.ret = zero
        self.status = jnp.full(batch, isa.STATUS_FELL_OFF, jnp.int64)
        self.steps = zero
        # fault record: a faulted lane halts, so at most one site fires
        # per lane and every latched column is a masked sum — each site
        # adds its index + 1, the runtime address (and the device /
        # chain step, when not static) under its fault lanes
        # (`_record_fault`), with no per-site selects.  `sites` is the
        # static side table the (pc, opcode, dev) columns are recovered
        # from at finalization (`_finalize_fault`); its last column is
        # the site *kind* (0 plain instruction, 1 gather chain,
        # 2 scatter-reduce, 3 map loop, 4 zip loop — fused sites recover
        # pc/opcode from the latched body-instruction index).  A site
        # inside a loop body is traced once and serves every trip.
        self.sites: List[Tuple[int, int, int, int]] = []
        self.f_site = self.f_addr = self.f_dev = self.f_aux = zero

    # -- small helpers ---------------------------------------------------

    def _full(self, v) -> jnp.ndarray:
        return jnp.full(self.B, v, jnp.int64)

    def _carried(self) -> Tuple:
        return (tuple(self.regs),) + tuple(getattr(self, n)
                                           for n in _CARRIED)

    def _restore(self, state: Tuple) -> None:
        self.regs = list(state[0])
        for n, v in zip(_CARRIED, state[1:]):
            setattr(self, n, v)

    def set_reg(self, idx: int, val, p) -> None:
        idx &= _REG_MASK
        self.regs[idx] = jnp.where(p, val, self.regs[idx])

    def dev_of(self, field: int, via_reg: bool) -> jnp.ndarray:
        if via_reg:
            d = self.regs[field & _REG_MASK]
            return jnp.where(d == DEV_LOCAL, self.homes,
                             jnp.mod(d, self.n_dev))
        if field == DEV_LOCAL:
            return self.homes
        return self._full(int(field) % self.n_dev)

    def word_addr(self, ins: Instr) -> jnp.ndarray:
        """LOAD/STORE/CAS/CAA all address ``region(a)[regs[b] + imm]``."""
        rid = ins.a
        off = self.regs[ins.b & _REG_MASK] + ins.imm
        return int(self.base[rid]) + (off & int(self.mask[rid]))

    # -- runtime protection ------------------------------------------------

    def _record_fault(self, site, f, addr, dev=None, aux=None) -> None:
        """Halt the lanes ``f``, add the static ``site`` row
        (pc, opcode, dev column, kind) to ``sites`` and its runtime
        columns to the fault record under ``f`` (see ``__init__``)."""
        self.halted = self.halted | f
        fi = f.astype(jnp.int64)
        self.f_site = self.f_site + fi * (len(self.sites) + 1)
        self.f_addr = self.f_addr + fi * addr
        if dev is not None:
            self.f_dev = self.f_dev + fi * dev
        if aux is not None:
            self.f_aux = self.f_aux + fi * aux
        self.sites.append(site)

    def _latch_fault(self, p, flt, pc, opcode, addr, dev=None):
        """Latch a protection fault on lanes ``p & flt``: halt them,
        record the static site index plus the runtime address (and the
        device, when it isn't statically known), and return the reduced
        predicate for the faulting instruction's own effects.  The
        (pc, opcode, dev) columns and STATUS_PROT_FAULT are recovered
        once at finalization — see `_finalize_fault`.

        ``dev``: None when the resolved device is statically the lane's
        home, an int when it is a static device id, or a traced (B,)
        array (latched into the record's device column)."""
        f = p & flt
        if dev is None:
            devcol, dtr = _DEV_HOME, None
        elif isinstance(dev, int):
            devcol, dtr = dev, None
        else:
            devcol, dtr = _DEV_LATCHED, dev
        self._record_fault((pc, int(opcode), devcol, 0), f, addr, dtr)
        return p ^ f

    def _word_fault(self, ins, p, pc):
        """Fault check shared by LOAD/STORE/CAS/CAA; mirrors pyvm's
        priority: wild device register, then out-of-region offset, then
        failed target device."""
        if not self.protect:
            return p
        via = bool(ins.flags & FLAG_DEV_REG)
        off = self.regs[ins.b & _REG_MASK] + ins.imm
        oob_off = off != (off & int(self.mask[ins.a]))
        if via:
            dev = self.dev_of(ins.e, True)
            draw = self.regs[ins.e & _REG_MASK]
            oob_dev = (draw != DEV_LOCAL) & ((draw < 0) | (draw >= self.n_dev))
            fdev = jnp.where(oob_dev, draw, dev)
            flt = oob_dev | oob_off
            if self.check_failed:
                flt = flt | self.failed[dev]
            return self._latch_fault(p, flt, pc, int(ins.op), off, fdev)
        flt = oob_off
        if self.check_failed:
            flt = flt | self.failed[self.dev_of(ins.e, False)]
        dev_static = None if ins.e == DEV_LOCAL \
            else int(ins.e) % self.n_dev
        return self._latch_fault(p, flt, pc, int(ins.op), off, dev_static)

    # -- per-opcode lowering ----------------------------------------------

    def _movi(self, ins, p):
        self.set_reg(ins.dst, self._full(ins.imm), p)

    def _alu(self, ins, p):
        rhs = self._full(ins.imm) if ins.flags & FLAG_IMMB \
            else self.regs[ins.b & _REG_MASK]
        self.set_reg(ins.dst, _alu_static(ins.d, self.regs[ins.a & _REG_MASK],
                                          rhs), p)

    def _load(self, ins, p, pc):
        p = self._word_fault(ins, p, pc)
        dev = self.dev_of(ins.e, bool(ins.flags & FLAG_DEV_REG))
        val = self.memf[dev * self.P + self.word_addr(ins)]
        self.set_reg(ins.dst, val, p)

    def _store(self, ins, p, pc):
        p = self._word_fault(ins, p, pc)
        dev = self.dev_of(ins.e, bool(ins.flags & FLAG_DEV_REG))
        idx = dev * self.P + self.word_addr(ins)
        self.memf = det_scatter(self.memf, idx,
                                self.regs[ins.dst & _REG_MASK], p)

    def _atomic(self, ins, p, pc, is_cas: bool):
        p = self._word_fault(ins, p, pc)
        dev = self.dev_of(ins.e, bool(ins.flags & FLAG_DEV_REG))
        idx = dev * self.P + self.word_addr(ins)
        cmpv = self.regs[ins.c & _REG_MASK]
        arg = self.regs[ins.d & _REG_MASK]
        size = self.memf.shape[0]

        def body(memf, x):
            i_b, cmp_b, arg_b, p_b = x
            old = memf[jnp.clip(i_b, 0, size - 1)]
            hit = (old == cmp_b) & p_b
            new = jnp.where(hit, arg_b if is_cas else old + arg_b, old)
            memf = memf.at[jnp.where(p_b, i_b, size)].set(new, mode="drop")
            return memf, old

        # atomics are serialized over the batch: pyvm request ordering
        self.memf, old = lax.scan(body, self.memf, (idx, cmpv, arg, p))
        self.set_reg(ins.dst, old, p)

    def _memcpy(self, ins, p, pc):
        via_d = bool(ins.flags & FLAG_DSTDEV_REG)
        via_s = bool(ins.flags & FLAG_SRCDEV_REG)
        ddev = self.dev_of(ins.dst, via_d)
        sdev = self.dev_of(ins.c, via_s)
        drid, srid = ins.a, ins.d
        cap = min(int(ins.imm), isa.MAX_MEMCPY_WORDS)
        if ins.flags & FLAG_LEN_REG:
            ln = jnp.clip(self.regs[ins.imm2 & _REG_MASK], 0, cap)
        else:
            ln = self._full(cap)
        ln = jnp.minimum(ln, min(int(self.mask[drid]) + 1,
                                 int(self.mask[srid]) + 1))
        if self.protect:
            doff0 = self.regs[ins.b & _REG_MASK]
            soff0 = self.regs[ins.e & _REG_MASK]
            dmask, smask = int(self.mask[drid]), int(self.mask[srid])

            def dev_oob(field, via):
                if not via:
                    return jnp.zeros(self.B, bool)
                d = self.regs[field & _REG_MASK]
                return (d != DEV_LOCAL) & ((d < 0) | (d >= self.n_dev))

            oob_dd = dev_oob(ins.dst, via_d)
            oob_sd = dev_oob(ins.c, via_s)
            d_oob = (doff0 != (doff0 & dmask)) | (doff0 + ln > dmask + 1)
            s_oob = (soff0 != (soff0 & smask)) | (soff0 + ln > smask + 1)
            flt = (ln > 0) & (oob_dd | oob_sd | d_oob | s_oob)
            faddr = jnp.where(oob_dd | (~oob_sd & d_oob), doff0, soff0)
            fdev = jnp.where(oob_dd, self.regs[ins.dst & _REG_MASK],
                             jnp.where(oob_sd, self.regs[ins.c & _REG_MASK],
                                       jnp.where(d_oob, ddev, sdev)))
            p = self._latch_fault(p, flt, pc, int(ins.op), faddr, fdev)
        if self.check_failed:
            fail = self.failed[ddev] | self.failed[sdev]
            err = self.regs[ERR_REG]
            self.regs[ERR_REG] = jnp.where(p & fail, err | 1, err)
            ln = jnp.where(fail | ~p, 0, ln)
        else:
            ln = jnp.where(p, ln, 0)
        iw = jnp.arange(cap, dtype=jnp.int64)[None, :]
        soff = self.regs[ins.e & _REG_MASK][:, None]
        doff = self.regs[ins.b & _REG_MASK][:, None]
        src = sdev[:, None] * self.P + int(self.base[srid]) + \
            ((soff + iw) & int(self.mask[srid]))
        dst = ddev[:, None] * self.P + int(self.base[drid]) + \
            ((doff + iw) & int(self.mask[drid]))
        vals = self.memf[src]
        live = iw < ln[:, None]
        self.memf = det_scatter(self.memf, dst, vals, live)

    # -- the gather-chain superoperator ------------------------------------

    def _fused_gather_chain(self, g: GatherChain, m, p) -> None:
        """One two-level batched gather for the whole loop: ids -> table ->
        pool rows -> destination window.  Commit order is (iteration,
        request) — identical to the lockstep engine.

        With ``double_buffer`` the iteration axis is split into
        ``DBUF_CHUNK``-sized chunks scheduled split-phase, the way the
        operator's *async* Memcpy issues on hardware: chunk ``k+1``'s
        row gather is emitted before chunk ``k``'s scatter, and every
        gather reads the pre-chain memory snapshot, so the two carry no
        data dependency and XLA is free to overlap transfer (scatter
        commit) with resolution (the next gather).  Bit-identical to the
        monolithic path by construction — the monolithic path *also*
        reads all rows pre-scatter."""
        B, P = self.B, self.P
        cap, W = g.cap, g.row_words
        jj = jnp.arange(cap, dtype=jnp.int64)[None, :]          # (1, cap)
        i0 = self.regs[g.i_reg][:, None]
        dst0 = self.regs[g.dst_reg][:, None]
        home = self.homes[:, None]
        valid = (jj < m[:, None]) & p[:, None]                  # (B, cap)

        ids_addr = int(self.base[g.ids_rid]) + \
            ((i0 + jj) & int(self.mask[g.ids_rid]))
        ids = self.memf[home * P + ids_addr]                    # (B, cap)
        tbl_addr = int(self.base[g.table_rid]) + \
            (ids & int(self.mask[g.table_rid]))
        paddr = self.memf[home * P + tbl_addr]                  # (B, cap)

        fail = self.failed[self.homes] if self.check_failed else None
        pool_base = int(self.base[g.pool_rid])
        pool_mask = int(self.mask[g.pool_rid])

        if self.protect:
            # Per-iteration fault scan: body instruction k in {1: load id,
            # 2: load translation, 3: memcpy row} can fault at iteration j;
            # the chain commits exactly the first j* iterations plus the
            # k*-1 committed instructions of iteration j*, mirroring the
            # un-fused engines instruction for instruction.
            dmask = int(self.mask[g.dst_rid])
            lnW = min(W, dmask + 1, pool_mask + 1)
            ids_off = i0 + jj                                   # raw (B, cap)
            doffs = dst0 + jj * W                               # raw (B, cap)
            c1 = ids_off != (ids_off & int(self.mask[g.ids_rid]))
            if fail is not None:
                c1 = fail[:, None] | c1
            c2 = ids != (ids & int(self.mask[g.table_rid]))
            d_oob = (doffs != (doffs & dmask)) | (doffs + lnW > dmask + 1)
            s_oob = (paddr != (paddr & pool_mask)) | \
                (paddr + lnW > pool_mask + 1)
            k_j = jnp.where(c1, 1, jnp.where(c2, 2,
                            jnp.where(d_oob | s_oob, 3, 0)))
            k_j = jnp.where(valid, k_j, 0)
            has = k_j > 0
            flt = jnp.any(has, axis=1)
            js = jnp.argmax(has, axis=1).astype(jnp.int64)
            jsc = js[:, None]
            kstar = jnp.take_along_axis(k_j, jsc, axis=1)[:, 0]
            a3 = jnp.where(jnp.take_along_axis(d_oob, jsc, axis=1)[:, 0],
                           jnp.take_along_axis(doffs, jsc, axis=1)[:, 0],
                           jnp.take_along_axis(paddr, jsc, axis=1)[:, 0])
            faddr = jnp.where(
                kstar == 1, jnp.take_along_axis(ids_off, jsc, axis=1)[:, 0],
                jnp.where(kstar == 2,
                          jnp.take_along_axis(ids, jsc, axis=1)[:, 0], a3))
            # body starts at pc+1, so pc of body instruction k* is
            # loop_pc + k* — recovered at finalization from the chain's
            # latched k* (the record's aux column)
            self._record_fault((g.loop_pc, 0, _DEV_HOME, 1), flt, faddr,
                               aux=kstar)
            m_eff = jnp.where(flt, js, m)
            live = valid & (jj < m_eff[:, None])
        else:
            if fail is not None:
                err = self.regs[ERR_REG]
                self.regs[ERR_REG] = jnp.where(p & fail & (m > 0),
                                               err | 1, err)
                live = valid & ~fail[:, None]
            else:
                live = valid
            flt = jnp.zeros(self.B, bool)
            js = kstar = None
            m_eff = m
        iw = jnp.arange(W, dtype=jnp.int64)
        mem0 = self.memf              # pre-chain snapshot: all rows read it

        def gather_rows(pa):
            src = home[:, :, None] * P + pool_base + \
                ((pa[:, :, None] + iw) & pool_mask)     # (B, chunk, W)
            return mem0[src]

        dst_addr = home[:, :, None] * P + int(self.base[g.dst_rid]) + \
            ((dst0[:, :, None] + jj[:, :, None] * W + iw)
             & int(self.mask[g.dst_rid]))
        wmask = jnp.broadcast_to(live[:, :, None], dst_addr.shape)

        if self.double_buffer and cap > DBUF_CHUNK:
            # split-phase schedule: rows for chunk k+1 are gathered
            # before chunk k's scatter is emitted
            bounds = list(range(0, cap, DBUF_CHUNK)) + [cap]
            rows_next = gather_rows(paddr[:, bounds[0]:bounds[1]])
            for k in range(len(bounds) - 1):
                lo, hi = bounds[k], bounds[k + 1]
                rows_k = rows_next
                if k + 2 < len(bounds):
                    rows_next = gather_rows(
                        paddr[:, bounds[k + 1]:bounds[k + 2]])
                # commit chunk k in (iteration, request, word) order
                self.memf = det_scatter(
                    self.memf,
                    jnp.transpose(dst_addr[:, lo:hi], (1, 0, 2)),
                    jnp.transpose(rows_k, (1, 0, 2)),
                    jnp.transpose(wmask[:, lo:hi], (1, 0, 2)))
        else:
            if self.impl in ("kernel", "kernel_interpret") \
                    and self.n_dev == 1 and (pool_mask + 1) % W == 0:
                # Route the row gather through the Pallas double-
                # indirection kernel: rows must be W-aligned in the pool
                # region (true for every translation table the
                # workloads build).  The kernel moves 32-bit words, so
                # each 64-bit row travels as 2W int32 halves.
                from repro.kernels.tiara_gather.kernel import \
                    tiara_gather_kernel
                pool_view = lax.bitcast_convert_type(
                    lax.dynamic_slice(self.memf, (pool_base,),
                                      (pool_mask + 1,)),
                    jnp.int32).reshape(-1, 2 * W)
                rows = tiara_gather_kernel(
                    pool_view,
                    ((paddr & pool_mask).reshape(-1) // W).astype(jnp.int32),
                    jnp.arange(B * cap, dtype=jnp.int32),
                    interpret=(self.impl == "kernel_interpret"),
                )
                rows = lax.bitcast_convert_type(
                    rows.reshape(B, cap, W, 2), jnp.int64)
            else:
                rows = gather_rows(paddr)                # (B, cap, W)
            # commit in (iteration, request, word) order = round-robin
            self.memf = det_scatter(self.memf,
                                    jnp.transpose(dst_addr, (1, 0, 2)),
                                    jnp.transpose(rows, (1, 0, 2)),
                                    jnp.transpose(wmask, (1, 0, 2)))

        # architectural register effects of the executed iterations; a
        # faulted lane commits the loads that retired before the fault
        if self.protect:
            n_id = jnp.where(flt, js + (kstar >= 2).astype(jnp.int64), m)
            n_pa = jnp.where(flt, js + (kstar >= 3).astype(jnp.int64), m)
            steps_n = jnp.where(flt, js * 5 + kstar, m * 5)
        else:
            n_id = n_pa = m
            steps_n = m * 5
        self.set_reg(g.i_reg, self.regs[g.i_reg] + m_eff, p)
        self.set_reg(g.dst_reg, self.regs[g.dst_reg] + m_eff * W, p)
        self.set_reg(g.id_reg,
                     jnp.take_along_axis(
                         ids, jnp.clip(n_id - 1, 0, cap - 1)[:, None],
                         axis=1)[:, 0], p & (n_id > 0))
        self.set_reg(g.paddr_reg,
                     jnp.take_along_axis(
                         paddr, jnp.clip(n_pa - 1, 0, cap - 1)[:, None],
                         axis=1)[:, 0], p & (n_pa > 0))
        self.steps = self.steps + jnp.where(p, steps_n, 0)

    # -- the scatter-reduce superoperator ---------------------------------

    def _fused_scatter_reduce(self, sr: ScatterReduce, m, p) -> None:
        """One snapshot gather + elementwise compare + scatter-add for
        the whole CAA loop.  Exact only because every accumulator
        address is touched at most once: within a lane by the static
        ``|stride| * cap <= acc region size`` check (emit_segment), and
        across lanes by the ``noconflict`` wave proof the build asserts
        — so each CAA's ``old`` equals the pre-loop snapshot value and
        the conditional add commutes into one scatter-add."""
        B, P = self.B, self.P
        cap, s = sr.cap, sr.stride
        it = jnp.arange(cap, dtype=jnp.int64)[None, :]          # (1, cap)
        i0 = self.regs[sr.i_reg][:, None]
        j0 = self.regs[sr.j_reg][:, None]
        home = self.homes[:, None]
        valid = (it < m[:, None]) & p[:, None]                  # (B, cap)
        src_off = i0 + it
        acc_off = j0 + it * s
        src_mask = int(self.mask[sr.src_rid])
        acc_mask = int(self.mask[sr.acc_rid])

        if self.protect:
            # per-iteration fault scan: body instruction k in {1: load,
            # 2: caa} can fault at iteration j; commit exactly the first
            # j* iterations (a faulting CAA has zero effect).
            c1 = src_off != (src_off & src_mask)
            if self.check_failed:
                c1 = self.failed[self.homes][:, None] | c1
            c2 = acc_off != (acc_off & acc_mask)
            k_j = jnp.where(c1, 1, jnp.where(c2, 2, 0))
            k_j = jnp.where(valid, k_j, 0)
            has = k_j > 0
            flt = jnp.any(has, axis=1)
            js = jnp.argmax(has, axis=1).astype(jnp.int64)
            jsc = js[:, None]
            kstar = jnp.take_along_axis(k_j, jsc, axis=1)[:, 0]
            faddr = jnp.where(
                kstar == 1, jnp.take_along_axis(src_off, jsc, axis=1)[:, 0],
                jnp.take_along_axis(acc_off, jsc, axis=1)[:, 0])
            self._record_fault((sr.loop_pc, 0, _DEV_HOME, 2), flt, faddr,
                               aux=kstar)
            m_eff = jnp.where(flt, js, m)
        else:
            flt = jnp.zeros(B, bool)
            js = kstar = None
            m_eff = m
        live = valid & (it < m_eff[:, None])

        mem0 = self.memf             # pre-loop snapshot (exactness above)
        v = mem0[home * P + int(self.base[sr.src_rid])
                 + (src_off & src_mask)]                        # (B, cap)
        acc_addr = home * P + int(self.base[sr.acc_rid]) + \
            (acc_off & acc_mask)
        old = mem0[acc_addr]                                    # (B, cap)
        hit = (old == self.regs[sr.cmp_reg][:, None]) & live
        delta = jnp.where(hit, v, jnp.zeros((), jnp.int64))
        size = self.memf.shape[0]
        tgt = jnp.where(live, acc_addr, size)
        self.memf = self.memf.at[tgt].add(delta, mode="drop")

        if self.protect:
            n_v = jnp.where(flt, js + (kstar >= 2).astype(jnp.int64), m)
            steps_n = jnp.where(flt, js * 4 + kstar, m * 4)
        else:
            n_v = m
            steps_n = m * 4
        self.set_reg(sr.i_reg, self.regs[sr.i_reg] + m_eff, p)
        self.set_reg(sr.j_reg, self.regs[sr.j_reg] + m_eff * s, p)
        self.set_reg(sr.v_reg,
                     jnp.take_along_axis(
                         v, jnp.clip(n_v - 1, 0, cap - 1)[:, None],
                         axis=1)[:, 0], p & (n_v > 0))
        self.set_reg(sr.old_reg,
                     jnp.take_along_axis(
                         old, jnp.clip(m_eff - 1, 0, cap - 1)[:, None],
                         axis=1)[:, 0], p & (m_eff > 0))
        self.steps = self.steps + jnp.where(p, steps_n, 0)

    # -- the map / zip-with superoperator ---------------------------------

    def _fused_map_loop(self, ml: MapLoop, m, p) -> None:
        """Window gather(s) + one elementwise ALU + one deterministic
        scatter in (iteration, request) commit order.  The gathers read
        the pre-loop snapshot; the matcher requires the destination
        region to differ from every source region, so within a lane no
        store feeds a later load, and across lanes the compiled path's
        standing no-conflict assumption applies (same class as the
        gather chain and plain STORE lowering)."""
        B, P = self.B, self.P
        cap = ml.cap
        body_len = 6 if ml.is_zip else 5
        it = jnp.arange(cap, dtype=jnp.int64)[None, :]          # (1, cap)
        i0 = self.regs[ml.i_reg][:, None]
        j0 = self.regs[ml.j_reg][:, None]
        home = self.homes[:, None]
        valid = (it < m[:, None]) & p[:, None]
        src_off = i0 + it
        dst_off = j0 + it
        src_mask = int(self.mask[ml.src_rid])
        dst_mask = int(self.mask[ml.dst_rid])
        src2_mask = int(self.mask[ml.src2_rid]) if ml.is_zip else 0
        store_k = 4 if ml.is_zip else 3

        if self.protect:
            c1 = src_off != (src_off & src_mask)
            if self.check_failed:
                c1 = self.failed[self.homes][:, None] | c1
            c2 = (src_off != (src_off & src2_mask)) if ml.is_zip \
                else jnp.zeros_like(c1)
            c_st = dst_off != (dst_off & dst_mask)
            k_j = jnp.where(c1, 1, jnp.where(c2, 2,
                            jnp.where(c_st, store_k, 0)))
            k_j = jnp.where(valid, k_j, 0)
            has = k_j > 0
            flt = jnp.any(has, axis=1)
            js = jnp.argmax(has, axis=1).astype(jnp.int64)
            jsc = js[:, None]
            kstar = jnp.take_along_axis(k_j, jsc, axis=1)[:, 0]
            faddr = jnp.where(
                kstar == store_k,
                jnp.take_along_axis(dst_off, jsc, axis=1)[:, 0],
                jnp.take_along_axis(src_off, jsc, axis=1)[:, 0])
            self._record_fault((ml.loop_pc, 0, _DEV_HOME,
                                4 if ml.is_zip else 3), flt, faddr,
                               aux=kstar)
            m_eff = jnp.where(flt, js, m)
        else:
            flt = jnp.zeros(B, bool)
            js = kstar = None
            m_eff = m
        live = valid & (it < m_eff[:, None])

        mem0 = self.memf
        a_vals = mem0[home * P + int(self.base[ml.src_rid])
                      + (src_off & src_mask)]                   # (B, cap)
        if ml.is_zip:
            b_vals = mem0[home * P + int(self.base[ml.src2_rid])
                          + (src_off & src2_mask)]
            rhs = b_vals
        elif ml.alu_imm is not None:
            rhs = jnp.full((B, cap), ml.alu_imm, jnp.int64)
            b_vals = None
        else:
            rhs = self.regs[ml.rhs_reg][:, None] + jnp.zeros(
                (B, cap), jnp.int64)
            b_vals = None
        c_vals = _alu_static(ml.alu_op, a_vals, rhs)
        dst_addr = home * P + int(self.base[ml.dst_rid]) + \
            (dst_off & dst_mask)
        # commit in (iteration, request) order = the engine's round robin
        self.memf = det_scatter(self.memf,
                                jnp.transpose(dst_addr, (1, 0)),
                                jnp.transpose(c_vals, (1, 0)),
                                jnp.transpose(live, (1, 0)))

        if self.protect:
            n_a = jnp.where(flt, js + (kstar >= 2).astype(jnp.int64), m)
            n_c = jnp.where(flt, js + (kstar >= store_k).astype(jnp.int64),
                            m)
            steps_n = jnp.where(flt, js * body_len + kstar, m * body_len)
        else:
            n_a = n_c = m
            steps_n = m * body_len
        self.set_reg(ml.i_reg, self.regs[ml.i_reg] + m_eff, p)
        self.set_reg(ml.j_reg, self.regs[ml.j_reg] + m_eff, p)
        self.set_reg(ml.a_reg,
                     jnp.take_along_axis(
                         a_vals, jnp.clip(n_a - 1, 0, cap - 1)[:, None],
                         axis=1)[:, 0], p & (n_a > 0))
        if ml.is_zip:
            n_b = jnp.where(flt, js + (kstar >= 3).astype(jnp.int64), m) \
                if self.protect else m
            self.set_reg(ml.b_reg,
                         jnp.take_along_axis(
                             b_vals, jnp.clip(n_b - 1, 0, cap - 1)[:, None],
                             axis=1)[:, 0], p & (n_b > 0))
        self.set_reg(ml.c_reg,
                     jnp.take_along_axis(
                         c_vals, jnp.clip(n_c - 1, 0, cap - 1)[:, None],
                         axis=1)[:, 0], p & (n_c > 0))
        self.steps = self.steps + jnp.where(p, steps_n, 0)

    # -- segment emission ---------------------------------------------------

    def emit_segment(self, lo: int, hi: int, pred) -> Dict[int, jnp.ndarray]:
        """Emit instructions [lo, hi) under ``pred``; returns the escape
        predicates {target_pc: lanes} for jumps leaving the segment."""
        escapes: Dict[int, jnp.ndarray] = {}
        resume: Dict[int, jnp.ndarray] = {}
        pc = lo
        while pc < hi:
            if pc in resume:
                pred = pred | resume.pop(pc)
            ins = self.instrs[pc]
            p = pred & ~self.halted

            if ins.op == Op.LOOP:
                l = self.loops[pc]
                body_hi = l.end + 1
                self.steps = self.steps + p      # LOOP itself runs once
                cap = int(ins.imm)
                if ins.flags & FLAG_MREG:
                    m = jnp.clip(self.regs[ins.b & _REG_MASK], 0, cap)
                else:
                    m = self._full(cap)
                g = match_gather_chain(self.instrs, l) if self.superops \
                    else None
                if g is not None:
                    self._fused_gather_chain(g, m, p)
                    pc = body_hi
                    continue
                if self.superops and cap > 0:
                    # scatter-reduce fusion is exact only under the wave
                    # conflict proof plus the static within-lane address-
                    # uniqueness check (see ScatterReduce docstring)
                    sr = match_scatter_reduce(self.instrs, l) \
                        if self.noconflict else None
                    if sr is not None and \
                            abs(sr.stride) * cap <= int(
                                self.mask[sr.acc_rid]) + 1:
                        self._fused_scatter_reduce(sr, m, p)
                        pc = body_hi
                        continue
                    ml = match_map_loop(self.instrs, l)
                    if ml is not None:
                        self._fused_map_loop(ml, m, p)
                        pc = body_hi
                        continue
                # one while_loop trip per iteration, while some lane is
                # live; the lanes that jump out of the body (the "break")
                # collect per static target
                exits = sorted({
                    j + 1 + self.instrs[j].imm2
                    for j in range(l.start, body_hi)
                    if self.instrs[j].op == Op.JUMP
                    and j + 1 + self.instrs[j].imm2 >= body_hi})
                no = jnp.zeros(self.B, bool)
                i_halted = 1 + _CARRIED.index("halted")

                def live(it, state, gone):
                    broken = functools.reduce(jnp.logical_or, gone, no)
                    return pred & (it < m) & ~broken & ~state[i_halted]

                def more(carry):
                    it, state, gone = carry
                    return (it < cap) & jnp.any(live(it, state, gone))

                def trip(carry):
                    it, state, gone = carry
                    self._restore(state)
                    esc = self.emit_segment(l.start, body_hi,
                                            live(it, state, gone))
                    assert set(esc) <= set(exits), (esc.keys(), exits)
                    return it + 1, self._carried(), tuple(
                        g | esc.get(t, no) for t, g in zip(exits, gone))

                _, state, gone = lax.while_loop(
                    more, trip, (jnp.int64(0), self._carried(),
                                 (no,) * len(exits)))
                self._restore(state)
                for tgt, ep in zip(exits, gone):
                    pred = pred & ~ep
                    out = resume if tgt < hi else escapes
                    out[tgt] = out.get(tgt, no) | ep
                pc = body_hi
                continue

            if ins.op == Op.JUMP:
                self.steps = self.steps + p
                if ins.d == int(Alu.ALWAYS):
                    take = p
                else:
                    lhs = self.regs[ins.a & _REG_MASK]
                    rhs = self._full(ins.imm) if ins.flags & FLAG_IMMB \
                        else self.regs[ins.b & _REG_MASK]
                    take = p & (_alu_static(ins.d, lhs, rhs) != 0)
                tgt = pc + 1 + ins.imm2
                pred = pred & ~take
                if tgt < hi:
                    resume[tgt] = resume.get(
                        tgt, jnp.zeros(self.B, bool)) | take
                else:
                    escapes[tgt] = escapes.get(
                        tgt, jnp.zeros(self.B, bool)) | take
                pc += 1
                continue

            self.steps = self.steps + p
            if ins.op in (Op.NOP, Op.WAIT):
                pass                     # WAIT has no functional effect
            elif ins.op == Op.MOVI:
                self._movi(ins, p)
            elif ins.op == Op.ALU:
                self._alu(ins, p)
            elif ins.op == Op.LOAD:
                self._load(ins, p, pc)
            elif ins.op == Op.STORE:
                self._store(ins, p, pc)
            elif ins.op == Op.MEMCPY:
                self._memcpy(ins, p, pc)
            elif ins.op == Op.CAS:
                self._atomic(ins, p, pc, True)
            elif ins.op == Op.CAA:
                self._atomic(ins, p, pc, False)
            elif ins.op == Op.RET:
                self.ret = jnp.where(p, self.regs[ins.a & _REG_MASK],
                                     self.ret)
                self.status = jnp.where(p, self._full(ins.imm), self.status)
                self.halted = self.halted | p
            else:
                raise CompileError(f"pc {pc}: unsupported opcode {ins.op}")
            pc += 1
        return escapes


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _finalize_fault(tracer: _Tracer):
    """Turn the latched fault record into (pc, opcode, addr, dev) rows
    and apply STATUS_PROT_FAULT once.  (A latched fault implies the
    lane halted before any RET could retire it, so the single status
    override is equivalent to a per-site status write.)"""
    B = tracer.B
    if not tracer.sites:
        none = jnp.zeros(B, jnp.int64)
        return tracer.status, jnp.stack([none - 1, none, none, none],
                                        axis=1)
    site = tracer.f_site - 1
    addr, devp, aux = tracer.f_addr, tracer.f_dev, tracer.f_aux
    pc_t, op_t, dev_t, kind_t = (jnp.asarray(np.asarray(col, np.int64))
                                 for col in zip(*tracer.sites))
    sidx = jnp.maximum(site, 0)
    pcs, opv, devc = pc_t[sidx], op_t[sidx], dev_t[sidx]
    kind = kind_t[sidx]
    chain = kind != 0
    # fused sites latch the faulting body-instruction index k* in `aux`;
    # body starts at loop_pc + 1, so the faulting pc is loop_pc + k*,
    # and the opcode follows from the shape (gather chain: k*=3 is the
    # MEMCPY; scatter-reduce: k*=2 is the CAA; map/zip loop: the STORE
    # sits at k*=3/4; everything earlier is a LOAD)
    f_pc = jnp.where(chain, pcs + aux, pcs)
    fused_op = jnp.where(
        kind == 1, jnp.where(aux == 3, int(Op.MEMCPY), int(Op.LOAD)),
        jnp.where(kind == 2,
                  jnp.where(aux == 2, int(Op.CAA), int(Op.LOAD)),
                  jnp.where(aux == jnp.where(kind == 4, 4, 3),
                            int(Op.STORE), int(Op.LOAD))))
    f_op = jnp.where(chain, fused_op, opv)
    f_dev = jnp.where(devc == _DEV_LATCHED, devp,
                      jnp.where(devc == _DEV_HOME, tracer.homes, devc))
    faulted = site >= 0
    status = jnp.where(faulted, isa.STATUS_PROT_FAULT, tracer.status)
    fault = jnp.stack([jnp.where(faulted, f_pc, -1),
                       jnp.where(faulted, f_op, 0), addr,
                       jnp.where(faulted, f_dev, 0)], axis=1)
    return status, fault


def build_compiled(op: VerifiedOperator, regions: RegionTable,
                   n_devices: int, batch: int, *, impl: str = "xla",
                   superops: bool = True, double_buffer: bool = False,
                   protect: bool = True, check_failed: bool = True,
                   noconflict: bool = False):
    """Trace-compile a verified operator; returns a jit-compiled
    ``f(mem, params, homes, failed) -> vm.VMResult`` with batched fields
    (the same signature as :func:`vm.build_batched_vm`).

    ``impl``: "xla" lowers the gather-chain superoperator to plain jnp
    gathers; "kernel" / "kernel_interpret" route row gathers through the
    ``tiara_gather`` Pallas kernel (rows must be row-aligned in the pool,
    which all stock translation tables are).

    ``double_buffer``: emit gather-chain superoperators as a chunked
    split-phase schedule (chunk k+1's row gather issued before chunk
    k's scatter — the compiled analogue of the operator's async Memcpy
    pipelining).  Bit-identical results; takes precedence over the
    kernel row-gather route for the chain.

    ``check_failed=False`` statically elides every failed-device check
    (the ``failed`` argument is accepted and ignored) — the variant the
    invoke path builds for the fault-free hot path, where no device is
    down and the per-op mask gather would be pure overhead.

    ``noconflict=True`` asserts the caller holds a registration-time
    proof (``access.prove_wave_noconflict``) that no word written by one
    request is touched by another in the waves this engine will run.
    It unlocks the scatter-reduce superoperator fusion, whose
    snapshot-read lowering is exact only under that proof.
    """
    instrs = isa.decode_program(op.code)
    loops = {l.pc: l for l in op.loops}
    base, mask, _ = regions.as_arrays()
    n_instr = len(instrs)
    n_dev = int(n_devices)
    B = int(batch)

    def run(mem, params, homes, failed):
        mem = jnp.asarray(mem, jnp.int64)
        pool_words = mem.shape[1]
        homes = jnp.asarray(homes, jnp.int64).reshape(B)
        failed = jnp.asarray(failed, jnp.bool_)
        params = jnp.asarray(params, jnp.int64).reshape(B, -1)
        regs = [params[:, i] if i < params.shape[1]
                else jnp.zeros(B, jnp.int64)
                for i in range(isa.NUM_REGS)]
        tracer = _Tracer(
            instrs=instrs, loops=loops, base=base, mask=mask, n_dev=n_dev,
            pool_words=int(pool_words), batch=B, homes=homes, failed=failed,
            mem_flat=mem.reshape(-1), regs=regs, impl=impl,
            superops=superops, double_buffer=double_buffer, protect=protect,
            check_failed=check_failed, noconflict=noconflict)
        esc = tracer.emit_segment(0, n_instr, jnp.ones(B, bool))
        assert not esc, "verifier admitted a jump past the program end"
        status, fault = _finalize_fault(tracer)
        return _vm.VMResult(
            mem=tracer.memf.reshape(n_dev, pool_words),
            ret=tracer.ret, status=status, steps=tracer.steps,
            regs=jnp.stack(tracer.regs, axis=1), fault=fault)

    return jax.jit(run)


_COMPILED_CACHE: Dict = {}


def compiled_cached(op: VerifiedOperator, regions: RegionTable,
                    n_dev: int, batch: int, impl: str = "xla",
                    superops: bool = True, double_buffer: bool = False,
                    protect: bool = True,
                    failed: Optional[Set[int]] = None,
                    noconflict: bool = False) -> bool:
    """True iff the compiled trace for this (op, batch) is already
    built (see :func:`vm.engine_cached`).  ``failed`` mirrors the invoke
    argument: the fault-free hot path (``failed=None``) and the
    degraded-mode path compile to different variants."""
    return _vm.engine_key(op, regions, n_dev, batch, impl, superops,
                          double_buffer, bool(protect),
                          failed is not None,
                          bool(noconflict)) in _COMPILED_CACHE


def _cached_compiled(op: VerifiedOperator, regions: RegionTable, n_dev: int,
                     batch: int, impl: str, superops: bool,
                     double_buffer: bool = False, protect: bool = True,
                     check_failed: bool = True, noconflict: bool = False):
    key = _vm.engine_key(op, regions, n_dev, batch, impl, superops,
                         double_buffer, bool(protect), bool(check_failed),
                         bool(noconflict))
    fn = _COMPILED_CACHE.get(key)
    if fn is None:
        fn = build_compiled(op, regions, n_dev, batch, impl=impl,
                            superops=superops, double_buffer=double_buffer,
                            protect=protect, check_failed=check_failed,
                            noconflict=noconflict)
        _COMPILED_CACHE[key] = fn
    return fn


def invoke_compiled(op: VerifiedOperator, regions: RegionTable,
                    mem: np.ndarray, params: Sequence[Sequence[int]],
                    *, homes: Union[int, Sequence[int]] = 0,
                    failed: Optional[Set[int]] = None, impl: str = "xla",
                    superops: bool = True, double_buffer: bool = False,
                    protect: bool = True, noconflict: bool = False,
                    block: bool = True) -> "_vm.BatchedInvokeResult":
    """Numpy-in/numpy-out batched execution on the compiled fast path
    (same contract as :func:`vm.invoke_batched`).  ``failed=None``
    selects the variant with every failed-device check statically
    elided — the fault-free hot path pays nothing for the fencing.
    ``noconflict=True`` asserts the wave conflict proof (see
    :func:`build_compiled`)."""
    p, h = _vm._marshal_batch(params, homes)
    fn = _cached_compiled(op, regions, int(mem.shape[0]), p.shape[0],
                          impl, superops, double_buffer, protect,
                          check_failed=failed is not None,
                          noconflict=noconflict)
    return _vm.run_batched_fn(fn, mem, p, h, failed, block=block)
