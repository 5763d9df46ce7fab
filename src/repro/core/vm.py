"""JAX execution engines for verified Tiara operators.

The paper's NIC pipeline keeps many requests in flight at line rate; the
software analogue is a *batch-parallel* execution engine.  One memory
processor (MP) frontend is modeled as a single ``lax.while_loop`` whose
carry is the architectural state of the paper's Fig. 4 datapath — pc, the
16x64 b register file, the depth-8 loop stack, the in-flight async counter
— for **B independent requests at once**, stepping against one shared
memory pool.  One XLA launch is amortized over the whole batch instead of
paying interpreter dispatch per request.

Execution semantics of a batch (deterministic round-robin interleaving):
each macro-step, every live request executes its current instruction in
request-index order, and request *i* observes all memory effects of
requests ``j < i`` within the same macro-step.  When the requests' memory
footprints are disjoint this is bit-identical to running them one after
another on the ``pyvm`` oracle; under contention (e.g. CAS on a shared
latch) the ordering stays deterministic — the lowest-indexed request wins.

Two step implementations share that semantics:

  * a fully vectorized step (active-mask semantics, every opcode computed
    for every lane and combined with masks, scatters routed through
    out-of-bounds drop lanes) used whenever a cheap per-step conflict
    check proves no request's write window can touch another's read or
    write window, and
  * an exact serialized step — a ``lax.scan`` over the batch of the
    scalar ``lax.switch`` interpreter — used for contended steps so
    atomics (STORE/CAS/CAA) keep pyvm ordering.

``build_vm`` (the single-request entry point every existing caller uses)
is the ``batch=1`` specialization of the same engine.

**Mixed-batch execution model** (the multi-tenant line-rate path): the
engine is built over a *merged instruction store* — every registered
program laid out back to back, exactly the registry's shared BRAM — and
each request additionally carries an ``op_sel`` slot index.  Request ``b``
starts at ``start_pc[op_sel[b]]``, terminates against its own program end
and its own verified step bound, and otherwise participates in the very
same lockstep macro-step: one ``lax.while_loop`` advances B requests
running *different tenants' operators* against the one shared pool, so a
serving wave interleaving GraphWalk, PageTableWalk, KV-fetch and MoE
requests costs one XLA launch instead of one launch per op_id.  The
per-step sweep-line conflict check and the serialized contended fallback
reason per-request from the decoded instruction rows, so mixed batches
compose with them unchanged: contended steps of a mixed batch keep the
deterministic lowest-index-wins ordering.  ``build_batched_vm`` is the
one-program specialization (``op_sel`` pinned to slot 0);
``build_mixed_batched_vm`` / ``invoke_batched_mixed`` expose the full
dispatch-table form.

**Sharded execution model** (the pod-scale fabric): the same lockstep
semantics run over a ``jax.sharding.Mesh`` with the pool's leading
``n_devices`` axis sharded (``shard_map``).  Each device executes the
home-bucketed sub-wave it owns; remote LOAD/MEMCPY lower to collectives
across the mesh axis, and contended macro-steps fall back to a
replicated serialized scan in global *arrival* order, so the
deterministic round-robin contention semantics survive sharding
bit-for-bit (``build_sharded_mixed_vm`` / ``invoke_sharded_mixed``).
The step semantics themselves are emitted once (``_make_scalar_step`` /
``_make_vector_step``) against a small memory-access interface, so the
dense and sharded engines cannot drift apart.

The *verified step bound* is the loop fuel: registration-time verification
proves the VM can never hit it, and the property tests assert exactly that.

Semantics are defined by ``repro.core.pyvm`` — keep the two in lockstep.
All ISA values are int64; because x64 mode is not enabled globally (model
code runs in default 32-bit mode), every entry point here wraps execution
in a local x64 configuration context.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import weakref
from typing import Dict, NamedTuple, Optional, Sequence, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import isa
from repro.core.isa import (Alu, Op, FLAG_ASYNC, FLAG_DEV_REG,
                            FLAG_DSTDEV_REG, FLAG_IMMB, FLAG_LEN_REG,
                            FLAG_MREG, FLAG_SRCDEV_REG, FLAG_THR_REG,
                            DEV_LOCAL, ERR_REG)
from repro.core.memory import RegionTable
from repro.core.verifier import VerifiedOperator

_REG_MASK = isa.NUM_REGS - 1


@contextlib.contextmanager
def x64():
    """Locally enable 64-bit mode (the ISA is 64-bit; models stay 32-bit)."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", old)


class ReqState(NamedTuple):
    """Per-request architectural state.  In the batched engine every leaf
    carries a leading batch dimension; the shared memory pool is threaded
    separately so B requests step against one pool."""

    pc: jnp.ndarray          # i64 [B]
    regs: jnp.ndarray        # i64 [B, 16]
    lstack: jnp.ndarray      # i64 [B, 8, 3]  (start, end, remaining)
    lsp: jnp.ndarray         # i64 [B]
    inflight: jnp.ndarray    # i64 [B]
    halted: jnp.ndarray      # bool [B]
    ret: jnp.ndarray         # i64 [B]
    status: jnp.ndarray      # i64 [B]
    steps: jnp.ndarray       # i64 [B]
    ctrl: jnp.ndarray        # i64 [B]: 0 = advance (loop-iterate), 1 = taken jump
    pc_new: jnp.ndarray      # i64 [B]
    fault: jnp.ndarray       # i64 [B, 4]: (pc, opcode, addr, device); pc=-1 none


class VMResult(NamedTuple):
    mem: jnp.ndarray
    ret: jnp.ndarray
    status: jnp.ndarray
    steps: jnp.ndarray
    regs: jnp.ndarray
    fault: jnp.ndarray       # i64 [B, 4] FaultInfo rows (pc=-1 = no fault)


# The "no fault" FaultInfo row every clean lane carries (pc = -1).
NO_FAULT = np.asarray([-1, 0, 0, 0], dtype=np.int64)


def fault_info(row) -> Optional[isa.FaultInfo]:
    """Decode one [4] fault row into a FaultInfo (None when clean)."""
    row = np.asarray(row, dtype=np.int64).reshape(4)
    if int(row[0]) < 0:
        return None
    return isa.FaultInfo(pc=int(row[0]), opcode=int(row[1]),
                         addr=int(row[2]), device=int(row[3]))


def _i64(x) -> jnp.ndarray:
    return jnp.asarray(x, dtype=jnp.int64)


def _alu_table(a, b):
    """All 16 ALU results for operands ``a``/``b`` (any common shape),
    indexed by ``Alu`` opcode — the single definition both the scalar and
    the vectorized evaluator select from."""
    sh = b & 63
    return [
        a + b, a - b, a * b, a & b, a | b, a ^ b,
        a << sh, lax.shift_right_logical(a, sh),
        (a == b).astype(jnp.int64), (a != b).astype(jnp.int64),
        (a < b).astype(jnp.int64), (a >= b).astype(jnp.int64),
        jnp.minimum(a, b), jnp.maximum(a, b), a, a,
    ]


# ---------------------------------------------------------------------------
# Memory access objects — the one seam between instruction semantics and
# the pool's physical layout.  The step emitters below are written
# against this small interface, so the identical semantics drive both
# the dense single-process pool and a mesh-sharded pool where every
# device holds one row and remote accesses lower to collectives.
# ---------------------------------------------------------------------------


class _DenseOps:
    """Direct access to the full ``(n_devices, pool_words)`` pool — the
    single-process engines."""

    def __init__(self, n_dev: int, pool_words: int):
        self.n_dev = n_dev
        self.P = pool_words

    # -- scalar (one lane; addresses verified in range, except that a
    # faulted lane routes dev to n_dev so the write drops) ---------------
    def read1(self, mem, dev, addr):
        return mem[dev, addr]

    def write1(self, mem, dev, addr, val):
        return mem.at[dev, addr].set(val, mode="drop")

    def read1_win(self, mem, dev, phys):
        return mem[dev, phys]

    def write1_win(self, mem, dev, idx, val):
        return mem.at[dev, idx].set(val, mode="drop")

    # -- vector (B lanes; dead lanes routed to drop targets) -------------
    def readv(self, mem, dev, addr):
        return mem[jnp.clip(dev, 0, self.n_dev - 1),
                   jnp.clip(addr, 0, self.P - 1)]

    def writev(self, mem, dev, addr, val, mask):
        return mem.at[jnp.where(mask, dev, self.n_dev),
                      jnp.where(mask, addr, self.P)].set(val, mode="drop")

    def readv_win(self, mem, dev, phys):
        return mem[jnp.clip(dev, 0, self.n_dev - 1)[:, None],
                   jnp.clip(phys, 0, self.P - 1)]

    def writev_win(self, mem, dev, phys, val, live):
        return mem.at[jnp.where(live, dev[:, None], self.n_dev),
                      jnp.where(live, phys, self.P)].set(val, mode="drop")

    def any_lane(self, flag):
        """Predicate for data-dependent ``lax.cond`` skips."""
        return jnp.any(flag)


class _ShardOps:
    """Collective-routed access to one device's pool shard inside
    ``shard_map``: ``mem`` is this device's ``(pool_words,)`` row of the
    ``(n_devices, pool_words)`` pool.

    Reads are answered by the owning shard (masked contribution +
    ``psum`` across the mesh axis); writes are applied only by the
    owner (non-owners route the scatter out of bounds and drop it).
    The *vector* ops route different per-device requests: indices are
    ``all_gather``-ed across the axis, every shard contributes the words
    it owns, and the ``psum`` carries each answer back — the software
    spelling of the fabric's remote-read round trip.  The *scalar* ops
    are called only from the replicated serialized fallback, where every
    device asks the identical question, so a masked ``psum`` suffices.

    ``any_lane`` returns a globally agreed predicate so data-dependent
    ``lax.cond`` skips take the same branch on every device (collectives
    inside a divergent branch would deadlock the mesh).
    """

    def __init__(self, n_dev: int, pool_words: int, axis: str, me):
        self.n_dev = n_dev
        self.P = pool_words
        self.axis = axis
        self.me = me

    # -- scalar (replicated callers) -------------------------------------
    def read1(self, mem, dev, addr):
        own = jnp.where(dev == self.me,
                        mem[jnp.clip(addr, 0, self.P - 1)], 0)
        return lax.psum(own, self.axis)

    def write1(self, mem, dev, addr, val):
        return mem.at[jnp.where(dev == self.me, addr, self.P)
                      ].set(val, mode="drop")

    def read1_win(self, mem, dev, phys):
        own = jnp.where(dev == self.me,
                        mem[jnp.clip(phys, 0, self.P - 1)], 0)
        return lax.psum(own, self.axis)

    def write1_win(self, mem, dev, idx, val):
        return mem.at[jnp.where(dev == self.me, idx, self.P)
                      ].set(val, mode="drop")

    # -- vector (per-device sub-waves; requests differ across devices) ---
    def readv(self, mem, dev, addr):
        req = lax.all_gather(jnp.stack([dev, addr]), self.axis)
        own = jnp.where(req[:, 0] == self.me,
                        mem[jnp.clip(req[:, 1], 0, self.P - 1)], 0)
        return jnp.take(lax.psum(own, self.axis), self.me, axis=0)

    def writev(self, mem, dev, addr, val, mask):
        pay = lax.all_gather(
            jnp.stack([dev, addr, val, mask.astype(jnp.int64)]), self.axis)
        d, a = pay[:, 0].reshape(-1), pay[:, 1].reshape(-1)
        v, m = pay[:, 2].reshape(-1), pay[:, 3].reshape(-1) != 0
        mine = m & (d == self.me)
        return mem.at[jnp.where(mine, jnp.clip(a, 0, self.P - 1), self.P)
                      ].set(v, mode="drop")

    def readv_win(self, mem, dev, phys):
        reqd = lax.all_gather(dev, self.axis)            # (n_dev, B)
        reqp = lax.all_gather(phys, self.axis)           # (n_dev, B, W)
        own = jnp.where(reqd[:, :, None] == self.me,
                        mem[jnp.clip(reqp, 0, self.P - 1)], 0)
        return jnp.take(lax.psum(own, self.axis), self.me, axis=0)

    def writev_win(self, mem, dev, phys, val, live):
        reqd = lax.all_gather(dev, self.axis)            # (n_dev, B)
        pay = lax.all_gather(
            jnp.stack([phys, val, live.astype(jnp.int64)], axis=0),
            self.axis)                                   # (n_dev, 3, B, W)
        a, v = pay[:, 0], pay[:, 1]
        lv = (pay[:, 2] != 0) & (reqd[:, :, None] == self.me)
        return mem.at[jnp.where(lv, jnp.clip(a, 0, self.P - 1), self.P)
                      ].set(v, mode="drop")

    def any_lane(self, flag):
        return lax.psum(jnp.any(flag).astype(jnp.int32), self.axis) > 0


# ---------------------------------------------------------------------------
# Step emitters, shared by the dense and sharded engines
# ---------------------------------------------------------------------------


def _make_scalar_step(*, base_c, mask_c, failed, n_dev, max_window, depth,
                      ops, protect=True):
    """The scalar (one-request) ``lax.switch`` interpreter — the semantic
    reference every other step implementation must match.  Memory access
    goes through ``ops``, so the same branches drive the dense pool and a
    mesh shard.  Returns ``step_one(s, mem, row, home, act)``.

    ``protect`` bakes in the runtime protection checks (see pyvm): a
    data-dependent device/offset outside the grant, or a word access to a
    failed device, halts the lane with ``STATUS_PROT_FAULT`` and masks
    every effect of the faulting instruction.  With ``protect=False`` the
    checks are not traced at all (legacy wrap semantics)."""

    def dev_of1(regs, home, field, via_reg):
        dreg = regs[field & _REG_MASK]
        d = jnp.where(via_reg, dreg, field)
        return jnp.where(d == DEV_LOCAL, home, jnp.mod(d, n_dev))

    def phys1(rid, off):
        return base_c[rid] + (off & mask_c[rid])

    def alu_eval1(aop, a, b):
        return jnp.stack(_alu_table(a, b))[jnp.clip(aop, 0, 15)]

    def advance(s: ReqState, **kw) -> ReqState:
        return s._replace(ctrl=_i64(0), pc_new=s.pc + 1, **kw)

    # --- runtime protection (scalar) -------------------------------------
    def dev_oob1(regs, field, via_reg):
        """Register-held device that is neither DEV_LOCAL nor a real id."""
        d = regs[field & _REG_MASK]
        return via_reg & (d != DEV_LOCAL) & ((d < 0) | (d >= n_dev))

    def word_fault1(s, home, row):
        """(fault?, FaultInfo row) for LOAD/STORE/CAS/CAA; (None, None)
        when protection is compiled out."""
        if not protect:
            return None, None
        via = (row[isa.F_FLAGS] & FLAG_DEV_REG) != 0
        draw = jnp.where(via, s.regs[row[isa.F_E] & _REG_MASK],
                         row[isa.F_E])
        oob_dev = dev_oob1(s.regs, row[isa.F_E], via)
        dev = dev_of1(s.regs, home, row[isa.F_E], via)
        off = s.regs[row[isa.F_B] & _REG_MASK] + row[isa.F_IMM]
        oob_off = off != (off & mask_c[row[isa.F_A]])
        flt = oob_dev | oob_off | failed[dev]
        frow = jnp.stack([s.pc, row[isa.F_OP], off,
                          jnp.where(oob_dev, draw, dev)])
        return flt, frow

    def prot_halt(s2: ReqState, s: ReqState, flt, frow) -> ReqState:
        """Merge: on fault keep ``s``'s architectural state (regs come
        pre-masked by the branch), halt with PROT_FAULT and latch the
        fault record."""
        if flt is None:
            return s2
        return s2._replace(
            halted=s2.halted | flt,
            status=jnp.where(flt, _i64(isa.STATUS_PROT_FAULT), s2.status),
            inflight=jnp.where(flt, s.inflight, s2.inflight),
            fault=jnp.where(flt, frow, s2.fault))

    # --- one branch per opcode; (s, mem, row, home) -> (s, mem) ----------
    def br_nop(s, mem, row, home):
        return advance(s), mem

    def br_movi(s, mem, row, home):
        return advance(s, regs=s.regs.at[row[isa.F_DST] & _REG_MASK]
                       .set(row[isa.F_IMM])), mem

    def br_alu(s, mem, row, home):
        rhs = jnp.where(row[isa.F_FLAGS] & FLAG_IMMB, row[isa.F_IMM],
                        s.regs[row[isa.F_B] & _REG_MASK])
        val = alu_eval1(row[isa.F_D], s.regs[row[isa.F_A] & _REG_MASK],
                        rhs)
        return advance(s, regs=s.regs.at[row[isa.F_DST] & _REG_MASK]
                       .set(val)), mem

    def br_load(s, mem, row, home):
        flt, frow = word_fault1(s, home, row)
        dev = dev_of1(s.regs, home, row[isa.F_E],
                      (row[isa.F_FLAGS] & FLAG_DEV_REG) != 0)
        addr = phys1(row[isa.F_A],
                     s.regs[row[isa.F_B] & _REG_MASK] + row[isa.F_IMM])
        val = ops.read1(mem, dev, addr)
        regs = s.regs.at[row[isa.F_DST] & _REG_MASK].set(val)
        if flt is not None:
            regs = jnp.where(flt, s.regs, regs)
        return prot_halt(advance(s, regs=regs), s, flt, frow), mem

    def br_store(s, mem, row, home):
        flt, frow = word_fault1(s, home, row)
        dev = dev_of1(s.regs, home, row[isa.F_E],
                      (row[isa.F_FLAGS] & FLAG_DEV_REG) != 0)
        addr = phys1(row[isa.F_A],
                     s.regs[row[isa.F_B] & _REG_MASK] + row[isa.F_IMM])
        val = s.regs[row[isa.F_DST] & _REG_MASK]
        if flt is not None:
            dev = jnp.where(flt, _i64(n_dev), dev)
        return prot_halt(advance(s), s, flt, frow), \
            ops.write1(mem, dev, addr, val)

    def br_memcpy(s, mem, row, home):
        flags = row[isa.F_FLAGS]
        via_d = (flags & FLAG_DSTDEV_REG) != 0
        via_s = (flags & FLAG_SRCDEV_REG) != 0
        ddev = dev_of1(s.regs, home, row[isa.F_DST], via_d)
        sdev = dev_of1(s.regs, home, row[isa.F_C], via_s)
        drid, srid = row[isa.F_A], row[isa.F_D]
        cap = row[isa.F_IMM]
        lnreg = s.regs[row[isa.F_IMM2] & _REG_MASK]
        ln = jnp.where(flags & FLAG_LEN_REG,
                       jnp.clip(lnreg, 0, cap), cap)
        ln = jnp.minimum(jnp.minimum(ln, mask_c[drid] + 1),
                         mask_c[srid] + 1)
        fail = failed[ddev] | failed[sdev]
        soff = s.regs[row[isa.F_E] & _REG_MASK]
        doff = s.regs[row[isa.F_B] & _REG_MASK]
        if protect:
            # Same 4-way priority as pyvm: dst-dev, src-dev, dst window,
            # src window.  Only a copy that would actually move words
            # (post-clamp ln > 0) can fault.
            oob_dd = dev_oob1(s.regs, row[isa.F_DST], via_d)
            oob_sd = dev_oob1(s.regs, row[isa.F_C], via_s)
            d_oob = (doff != (doff & mask_c[drid])) | \
                (doff + ln > mask_c[drid] + 1)
            s_oob = (soff != (soff & mask_c[srid])) | \
                (soff + ln > mask_c[srid] + 1)
            flt = (ln > 0) & (oob_dd | oob_sd | d_oob | s_oob)
            faddr = jnp.where(oob_dd | (~oob_sd & d_oob), doff, soff)
            fdev = jnp.where(
                oob_dd, s.regs[row[isa.F_DST] & _REG_MASK],
                jnp.where(oob_sd, s.regs[row[isa.F_C] & _REG_MASK],
                          jnp.where(d_oob, ddev, sdev)))
            frow = jnp.stack([s.pc, row[isa.F_OP], faddr, fdev])
            fail = fail & ~flt
        else:
            flt, frow = None, None
        ln = jnp.where(fail, 0, ln)
        if flt is not None:
            ln = jnp.where(flt, 0, ln)
        i = jnp.arange(max_window, dtype=jnp.int64)
        sphys = base_c[srid] + ((soff + i) & mask_c[srid])
        dphys = base_c[drid] + ((doff + i) & mask_c[drid])
        svals = ops.read1_win(mem, sdev, sphys)
        live = i < ln
        # Masked lanes all write the lane-0 value to the lane-0 slot so
        # duplicate scatter indices always carry identical values.
        val0 = jnp.where(ln > 0, svals[0], ops.read1(mem, ddev, dphys[0]))
        w_idx = jnp.where(live, dphys, dphys[0])
        w_val = jnp.where(live, svals, val0)
        mem2 = ops.write1_win(mem, ddev, w_idx, w_val)
        err = jnp.where(fail, s.regs[ERR_REG] | 1, s.regs[ERR_REG])
        regs = s.regs.at[ERR_REG].set(err)
        inflight = jnp.where(
            flags & FLAG_ASYNC,
            jnp.minimum(s.inflight + 1, isa.MAX_INFLIGHT), s.inflight)
        return prot_halt(advance(s, regs=regs, inflight=inflight),
                         s, flt, frow), mem2

    def _br_casa(s, mem, row, home, is_cas):
        flt, frow = word_fault1(s, home, row)
        dev = dev_of1(s.regs, home, row[isa.F_E],
                      (row[isa.F_FLAGS] & FLAG_DEV_REG) != 0)
        addr = phys1(row[isa.F_A],
                     s.regs[row[isa.F_B] & _REG_MASK] + row[isa.F_IMM])
        old = ops.read1(mem, dev, addr)
        hit = old == s.regs[row[isa.F_C] & _REG_MASK]
        swp = s.regs[row[isa.F_D] & _REG_MASK]
        new = jnp.where(hit, swp if is_cas else old + swp, old)
        regs = s.regs.at[row[isa.F_DST] & _REG_MASK].set(old)
        if flt is not None:
            regs = jnp.where(flt, s.regs, regs)
            dev = jnp.where(flt, _i64(n_dev), dev)
        return prot_halt(advance(s, regs=regs), s, flt, frow), \
            ops.write1(mem, dev, addr, new)

    def br_cas(s, mem, row, home):
        return _br_casa(s, mem, row, home, True)

    def br_caa(s, mem, row, home):
        return _br_casa(s, mem, row, home, False)

    def br_jump(s, mem, row, home):
        cond = row[isa.F_D]
        lhs = s.regs[row[isa.F_A] & _REG_MASK]
        rhs = jnp.where(row[isa.F_FLAGS] & FLAG_IMMB, row[isa.F_IMM],
                        s.regs[row[isa.F_B] & _REG_MASK])
        take = jnp.where(
            cond == int(Alu.ALWAYS), True,
            jnp.where(cond == int(Alu.EQ), lhs == rhs,
                      jnp.where(cond == int(Alu.NE), lhs != rhs,
                                jnp.where(cond == int(Alu.LT), lhs < rhs,
                                          lhs >= rhs))))
        return s._replace(
            ctrl=jnp.where(take, _i64(1), _i64(0)),
            pc_new=jnp.where(take, s.pc + 1 + row[isa.F_IMM2],
                             s.pc + 1)), mem

    def br_loop(s, mem, row, home):
        cap = row[isa.F_IMM]
        m = jnp.where(row[isa.F_FLAGS] & FLAG_MREG,
                      jnp.clip(s.regs[row[isa.F_B] & _REG_MASK], 0, cap),
                      cap)
        skip = m <= 0
        frame = jnp.stack([s.pc + 1, s.pc + row[isa.F_IMM2], m])
        sp = jnp.clip(s.lsp, 0, depth - 1)
        pushed = s.lstack.at[sp].set(frame)
        return s._replace(
            lstack=jnp.where(skip, s.lstack, pushed),
            lsp=jnp.where(skip, s.lsp, s.lsp + 1),
            ctrl=_i64(0),
            pc_new=jnp.where(skip, s.pc + 1 + row[isa.F_IMM2],
                             s.pc + 1)), mem

    def br_wait(s, mem, row, home):
        thr = jnp.where(row[isa.F_FLAGS] & FLAG_THR_REG,
                        s.regs[row[isa.F_A] & _REG_MASK],
                        row[isa.F_IMM])
        return advance(s, inflight=jnp.minimum(
            s.inflight, jnp.maximum(thr, 0))), mem

    def br_ret(s, mem, row, home):
        return advance(s, halted=jnp.asarray(True),
                       ret=s.regs[row[isa.F_A] & _REG_MASK],
                       status=row[isa.F_IMM]), mem

    branches = [br_nop, br_movi, br_alu, br_load, br_store, br_memcpy,
                br_cas, br_caa, br_jump, br_loop, br_wait, br_ret]

    # --- post-step loop bookkeeping (scalar) -----------------------------
    def loop_fixup1(s: ReqState) -> ReqState:
        # taken jump: pop every frame whose body the jump escaped
        def pop_cond(t):
            lsp, = t
            return (lsp > 0) & (s.lstack[jnp.maximum(lsp - 1, 0), 1]
                                < s.pc_new)

        def pop_body(t):
            lsp, = t
            return (lsp - 1,)

        (pop_lsp,) = lax.while_loop(pop_cond, pop_body, (s.lsp,))

        # normal advance: iterate / pop frames whose body just ended
        def it_cond(t):
            stack, lsp, pcn, done = t
            top_end = stack[jnp.maximum(lsp - 1, 0), 1]
            return (~done) & (lsp > 0) & (pcn == top_end + 1)

        def it_body(t):
            stack, lsp, pcn, done = t
            idx = jnp.maximum(lsp - 1, 0)
            rem = stack[idx, 2] - 1
            cont = rem > 0
            stack2 = stack.at[idx, 2].set(rem)
            return (jnp.where(cont, stack2, stack),
                    jnp.where(cont, lsp, lsp - 1),
                    jnp.where(cont, stack[idx, 0], pcn),
                    cont)

        it_stack, it_lsp, it_pcn, _ = lax.while_loop(
            it_cond, it_body,
            (s.lstack, s.lsp, s.pc_new, jnp.asarray(False)))

        is_jump = s.ctrl == 1
        return s._replace(
            pc=jnp.where(is_jump, s.pc_new, it_pcn),
            lsp=jnp.where(is_jump, pop_lsp, it_lsp),
            lstack=jnp.where(is_jump, s.lstack, it_stack))

    def step_one(s: ReqState, mem, row, home, act):
        """Execute one instruction of one request (if active)."""
        def do(args):
            s, mem = args
            opc = jnp.clip(row[isa.F_OP], 0,
                           len(branches) - 1).astype(jnp.int32)
            s2, mem2 = lax.switch(opc, branches, s, mem, row, home)
            s2 = s2._replace(steps=s2.steps + 1)
            s2 = lax.cond(s2.halted, lambda t: t, loop_fixup1, s2)
            return s2, mem2

        return lax.cond(act, do, lambda a: a, (s, mem))

    return step_one


def _serial_step_fn(step_one):
    """The contention-exact macro-step: requests 0..B-1 each execute one
    instruction in lane order against the shared pool."""
    def serial_step(s: ReqState, mem, rows, homes, active):
        def body(mem, x):
            s1, row, home, act = x
            s2, mem2 = step_one(s1, mem, row, home, act)
            return mem2, s2

        mem2, s2 = lax.scan(body, mem, (s, rows, homes, active))
        return s2, mem2

    return serial_step


def _sweep_conflict(r_lo, r_hi, w_lo, w_hi):
    """Conflict existence over per-lane footprint intervals (see
    ``lane_intervals``): does some lane's write window overlap another
    lane's read or write window?  A sweep line over the sorted interval
    starts with exclusive running maxima of the ends — O(L log L)."""
    big = jnp.int64(1) << 62
    empty_hi = -big
    L = r_lo.shape[0]
    lo = jnp.concatenate([r_lo, w_lo])
    hi = jnp.concatenate([r_hi, w_hi])
    isw = jnp.concatenate([jnp.zeros(L, bool), jnp.ones(L, bool)])
    order = jnp.argsort(lo)
    lo_s, hi_s, w_s = lo[order], hi[order], isw[order]
    hi_w = jnp.where(w_s, hi_s, empty_hi)
    neg1 = jnp.full(1, empty_hi)
    excl_all = jnp.concatenate([neg1, lax.cummax(hi_s)[:-1]])
    excl_w = jnp.concatenate([neg1, lax.cummax(hi_w)[:-1]])
    return jnp.any(excl_w > lo_s) | \
        jnp.any(w_s & (excl_all > lo_s))


def _make_vector_step(*, base_c, mask_c, n_regions, n_dev, pool_words,
                      max_window, depth, B, homes, failed, ops,
                      protect=True):
    """The vectorized macro-step plus the per-lane footprint intervals
    feeding the conflict sweep, parameterized over memory access.
    Returns ``(vector_step, lane_intervals)``.

    Every opcode path is computed for every lane and combined with
    masks; scatters route dead lanes to out-of-bounds drop targets.
    With ``protect`` (the default) the runtime protection checks of the
    scalar reference are decoded per lane and a faulting lane halts with
    ``STATUS_PROT_FAULT``, all channels masked.
    """
    lane16 = jnp.arange(isa.NUM_REGS, dtype=jnp.int64)[None, :]
    lane8 = jnp.arange(depth, dtype=jnp.int64)[None, :]

    def rd(regs, idx):
        """Vector register-file read: regs[b, idx[b] & 15]."""
        return jnp.take_along_axis(
            regs, (idx & _REG_MASK)[:, None], axis=1)[:, 0]

    def dev_of_v(regs, field, via_reg):
        d = jnp.where(via_reg, rd(regs, field), field)
        return jnp.where(d == DEV_LOCAL, homes, jnp.mod(d, n_dev))

    def dev_oob_v(regs, field, via_reg):
        d = rd(regs, field)
        return via_reg & (d != DEV_LOCAL) & ((d < 0) | (d >= n_dev))

    def _decode(s, rows):
        """Shared per-lane decode of memory operands (word ops and
        memcpy windows) used by both the vector step and the conflict
        check."""
        flags = rows[:, isa.F_FLAGS]
        # word ops (LOAD/STORE/CAS/CAA) share the same addressing form
        w_rid = jnp.clip(rows[:, isa.F_A], 0, n_regions - 1)
        w_via = (flags & FLAG_DEV_REG) != 0
        w_dev = dev_of_v(s.regs, rows[:, isa.F_E], w_via)
        w_off = rd(s.regs, rows[:, isa.F_B]) + rows[:, isa.F_IMM]
        w_addr = base_c[w_rid] + (w_off & mask_c[w_rid])
        # memcpy operands
        m_drid = jnp.clip(rows[:, isa.F_A], 0, n_regions - 1)
        m_srid = jnp.clip(rows[:, isa.F_D], 0, n_regions - 1)
        m_via_d = (flags & FLAG_DSTDEV_REG) != 0
        m_via_s = (flags & FLAG_SRCDEV_REG) != 0
        m_ddev = dev_of_v(s.regs, rows[:, isa.F_DST], m_via_d)
        m_sdev = dev_of_v(s.regs, rows[:, isa.F_C], m_via_s)
        cap = rows[:, isa.F_IMM]
        lnreg = rd(s.regs, rows[:, isa.F_IMM2])
        ln = jnp.where((flags & FLAG_LEN_REG) != 0,
                       jnp.clip(lnreg, 0, cap), cap)
        ln = jnp.minimum(jnp.minimum(ln, mask_c[m_drid] + 1),
                         mask_c[m_srid] + 1)
        m_fail = failed[m_ddev] | failed[m_sdev]
        m_soff = rd(s.regs, rows[:, isa.F_E])
        m_doff = rd(s.regs, rows[:, isa.F_B])
        out = dict(flags=flags, w_rid=w_rid, w_dev=w_dev, w_addr=w_addr,
                   m_drid=m_drid, m_srid=m_srid, m_ddev=m_ddev,
                   m_sdev=m_sdev, m_fail=m_fail, m_soff=m_soff,
                   m_doff=m_doff)
        if protect:
            # word-op fault columns (mirrors the scalar word_fault1)
            w_draw = jnp.where(w_via, rd(s.regs, rows[:, isa.F_E]),
                               rows[:, isa.F_E])
            w_oob_dev = dev_oob_v(s.regs, rows[:, isa.F_E], w_via)
            w_flt = w_oob_dev | (w_off != (w_off & mask_c[w_rid])) | \
                failed[w_dev]
            # memcpy fault columns (4-way priority, pre-fail-zero ln)
            oob_dd = dev_oob_v(s.regs, rows[:, isa.F_DST], m_via_d)
            oob_sd = dev_oob_v(s.regs, rows[:, isa.F_C], m_via_s)
            d_oob = (m_doff != (m_doff & mask_c[m_drid])) | \
                (m_doff + ln > mask_c[m_drid] + 1)
            s_oob = (m_soff != (m_soff & mask_c[m_srid])) | \
                (m_soff + ln > mask_c[m_srid] + 1)
            m_flt = (ln > 0) & (oob_dd | oob_sd | d_oob | s_oob)
            m_faddr = jnp.where(oob_dd | (~oob_sd & d_oob), m_doff,
                                m_soff)
            m_fdev = jnp.where(
                oob_dd, rd(s.regs, rows[:, isa.F_DST]),
                jnp.where(oob_sd, rd(s.regs, rows[:, isa.F_C]),
                          jnp.where(d_oob, m_ddev, m_sdev)))
            m_fail = m_fail & ~m_flt
            ln = jnp.where(m_flt, 0, ln)
            out.update(w_flt=w_flt, w_off=w_off,
                       w_fdev=jnp.where(w_oob_dev, w_draw, w_dev),
                       m_flt=m_flt, m_faddr=m_faddr, m_fdev=m_fdev,
                       m_fail=m_fail)
        ln = jnp.where(m_fail, 0, ln)
        out["ln"] = ln
        return out

    def lane_intervals(s, rows, active):
        """Per-lane read/write footprint intervals in flat
        ``dev * pool_words + addr`` coordinates.

        Word ops contribute exact one-word intervals; memcpy its exact
        window when it does not wrap the region mask, else the whole
        region.  An atomic's read is the same word as its write, so it
        contributes one write interval only.  The only false positive is
        a memcpy whose *own* source and destination windows overlap
        (memmove within one request), which merely takes the exact
        serialized path.  Never unsound."""
        d = _decode(s, rows)
        opv = rows[:, isa.F_OP]
        is_load = active & (opv == int(Op.LOAD))
        is_store = active & (opv == int(Op.STORE))
        is_atom = active & ((opv == int(Op.CAS)) | (opv == int(Op.CAA)))
        is_mcpy = active & (opv == int(Op.MEMCPY))
        P = pool_words
        wf = d["w_dev"] * P + d["w_addr"]
        # memcpy source span
        s_size = mask_c[d["m_srid"]] + 1
        s_start = d["m_soff"] & mask_c[d["m_srid"]]
        s_wrap = (s_start + d["ln"]) > s_size
        src_lo = d["m_sdev"] * P + base_c[d["m_srid"]] + \
            jnp.where(s_wrap, 0, s_start)
        src_hi = src_lo + jnp.where(s_wrap, s_size, d["ln"])
        # memcpy destination span
        d_size = mask_c[d["m_drid"]] + 1
        d_start = d["m_doff"] & mask_c[d["m_drid"]]
        d_wrap = (d_start + d["ln"]) > d_size
        dst_lo = d["m_ddev"] * P + base_c[d["m_drid"]] + \
            jnp.where(d_wrap, 0, d_start)
        dst_hi = dst_lo + jnp.where(d_wrap, d_size, d["ln"])

        big = jnp.int64(1) << 62
        empty_lo, empty_hi = big, -big
        r_lo = jnp.where(is_load, wf,
                         jnp.where(is_mcpy, src_lo, empty_lo))
        r_hi = jnp.where(is_load, wf + 1,
                         jnp.where(is_mcpy, src_hi, empty_hi))
        w_lo = jnp.where(is_store | is_atom, wf,
                         jnp.where(is_mcpy, dst_lo, empty_lo))
        w_hi = jnp.where(is_store | is_atom, wf + 1,
                         jnp.where(is_mcpy, dst_hi, empty_hi))
        # zero-length memcpy windows must be empty, not points
        r_hi = jnp.where(r_hi <= r_lo, empty_hi, r_hi)
        w_hi = jnp.where(w_hi <= w_lo, empty_hi, w_hi)
        return r_lo, r_hi, w_lo, w_hi

    def alu_eval_v(aop, a, b):
        stacked = jnp.stack(_alu_table(a, b))      # (16, B)
        return jnp.take_along_axis(
            stacked, jnp.clip(aop, 0, 15)[None, :], axis=0)[0]

    def vector_step(s: ReqState, mem, rows, active):
        d = _decode(s, rows)
        opv = rows[:, isa.F_OP]
        flags = d["flags"]
        imm = rows[:, isa.F_IMM]
        imm2 = rows[:, isa.F_IMM2]

        def is_op(o):
            return active & (opv == int(o))

        is_movi, is_alu = is_op(Op.MOVI), is_op(Op.ALU)
        is_load, is_store = is_op(Op.LOAD), is_op(Op.STORE)
        is_mcpy = is_op(Op.MEMCPY)
        is_cas, is_caa = is_op(Op.CAS), is_op(Op.CAA)
        is_jump, is_loop = is_op(Op.JUMP), is_op(Op.LOOP)
        is_wait, is_ret = is_op(Op.WAIT), is_op(Op.RET)
        is_atom = is_cas | is_caa

        # --- runtime protection faults -----------------------------
        if protect:
            flt = (d["w_flt"] & (is_load | is_store | is_atom)) | \
                (d["m_flt"] & is_mcpy)
            f_addr = jnp.where(is_mcpy, d["m_faddr"], d["w_off"])
            f_dev = jnp.where(is_mcpy, d["m_fdev"], d["w_fdev"])
            frows = jnp.stack(
                [s.pc, opv, f_addr, f_dev], axis=-1)       # (B, 4)
        else:
            flt = jnp.zeros(B, bool)

        # --- ALU / MOVI --------------------------------------------
        alu_rhs = jnp.where((flags & FLAG_IMMB) != 0, imm,
                            rd(s.regs, rows[:, isa.F_B]))
        alu_val = alu_eval_v(rows[:, isa.F_D],
                             rd(s.regs, rows[:, isa.F_A]), alu_rhs)

        # --- LOAD / CAS / CAA reads (step-start memory: the conflict
        # check guarantees no same-step writer touches these words).
        # Gated like the memcpy route: on a macro-step with no live
        # word-memory lane the read values are fully masked out below,
        # so skip the route entirely — in the sharded engine that is an
        # all_gather + psum saved on every compute-only step (the
        # predicate is globally agreed, so the mesh cannot diverge).
        def read_words(m):
            return ops.readv(m, d["w_dev"], d["w_addr"])

        w_old = lax.cond(ops.any_lane(is_load | is_atom), read_words,
                         lambda m: jnp.zeros(B, jnp.int64), mem)
        hit = w_old == rd(s.regs, rows[:, isa.F_C])
        swp = rd(s.regs, rows[:, isa.F_D])
        atom_new = jnp.where(
            hit, jnp.where(is_cas, swp, w_old + swp), w_old)

        # --- register write channel (one per opcode at most) --------
        err_old = s.regs[:, ERR_REG]
        err_new = jnp.where(d["m_fail"], err_old | 1, err_old)
        reg_w_mask = (is_movi | is_alu | is_load | is_atom | is_mcpy) \
            & ~flt
        reg_w_idx = jnp.where(
            is_mcpy, ERR_REG, rows[:, isa.F_DST] & _REG_MASK)
        reg_w_val = jnp.where(
            is_movi, imm,
            jnp.where(is_alu, alu_val,
                      jnp.where(is_load, w_old,
                                jnp.where(is_atom, w_old, err_new))))
        upd = (lane16 == reg_w_idx[:, None]) & reg_w_mask[:, None]
        regs = jnp.where(upd, reg_w_val[:, None], s.regs)

        # --- single-word scatter (STORE / CAS / CAA) -----------------
        sw_mask = (is_store | is_atom) & ~flt
        sw_val = jnp.where(is_store, rd(s.regs, rows[:, isa.F_DST]),
                           atom_new)
        mem = lax.cond(
            ops.any_lane(sw_mask),
            lambda m: ops.writev(m, d["w_dev"], d["w_addr"], sw_val,
                                 sw_mask),
            lambda m: m, mem)

        # --- memcpy window gather + scatter --------------------------
        # The window machinery materializes (B, max_window) gathers —
        # with a merged multi-tenant store max_window is the largest
        # cap of *any* program, so skip it entirely on the (frequent)
        # macro-steps where no live lane is copying.
        def do_memcpy(mem):
            iw = jnp.arange(max_window, dtype=jnp.int64)[None, :]
            sphys = base_c[d["m_srid"]][:, None] + \
                ((d["m_soff"][:, None] + iw)
                 & mask_c[d["m_srid"]][:, None])
            dphys = base_c[d["m_drid"]][:, None] + \
                ((d["m_doff"][:, None] + iw)
                 & mask_c[d["m_drid"]][:, None])
            live = is_mcpy[:, None] & (iw < d["ln"][:, None])
            svals = ops.readv_win(mem, d["m_sdev"], sphys)
            return ops.writev_win(mem, d["m_ddev"], dphys, svals, live)

        mem = lax.cond(ops.any_lane(is_mcpy), do_memcpy, lambda m: m, mem)

        # --- inflight ------------------------------------------------
        inflight = jnp.where(
            is_mcpy & ~flt & ((flags & FLAG_ASYNC) != 0),
            jnp.minimum(s.inflight + 1, isa.MAX_INFLIGHT), s.inflight)
        thr = jnp.where((flags & FLAG_THR_REG) != 0,
                        rd(s.regs, rows[:, isa.F_A]), imm)
        inflight = jnp.where(
            is_wait, jnp.minimum(inflight, jnp.maximum(thr, 0)),
            inflight)

        # --- RET / protection fault ----------------------------------
        halted = s.halted | is_ret | flt
        ret = jnp.where(is_ret, rd(s.regs, rows[:, isa.F_A]), s.ret)
        status = jnp.where(
            is_ret, imm,
            jnp.where(flt, _i64(isa.STATUS_PROT_FAULT), s.status))

        # --- control flow -------------------------------------------
        jcond = rows[:, isa.F_D]
        jlhs = rd(s.regs, rows[:, isa.F_A])
        jrhs = jnp.where((flags & FLAG_IMMB) != 0, imm,
                         rd(s.regs, rows[:, isa.F_B]))
        take = jnp.where(
            jcond == int(Alu.ALWAYS), True,
            jnp.where(jcond == int(Alu.EQ), jlhs == jrhs,
                      jnp.where(jcond == int(Alu.NE), jlhs != jrhs,
                                jnp.where(jcond == int(Alu.LT),
                                          jlhs < jrhs, jlhs >= jrhs))))
        # LOOP push
        cap = imm
        m = jnp.where((flags & FLAG_MREG) != 0,
                      jnp.clip(rd(s.regs, rows[:, isa.F_B]), 0, cap),
                      cap)
        skip = m <= 0
        push = is_loop & ~skip
        frame = jnp.stack([s.pc + 1, s.pc + imm2, m], axis=-1)  # (B, 3)
        sp = jnp.clip(s.lsp, 0, depth - 1)
        push_lane = (lane8 == sp[:, None]) & push[:, None]      # (B, 8)
        lstack = jnp.where(push_lane[:, :, None], frame[:, None, :],
                           s.lstack)
        lsp = jnp.where(push, s.lsp + 1, s.lsp)

        pc_new = jnp.where(
            is_jump & take, s.pc + 1 + imm2,
            jnp.where(is_loop & skip, s.pc + 1 + imm2, s.pc + 1))
        ctrl = jnp.where(is_jump & take, _i64(1), _i64(0))

        # --- loop fixup, vectorized over the batch -------------------
        def top(field, stk, lsp_v):
            idx = jnp.clip(lsp_v - 1, 0, depth - 1)
            return jnp.take_along_axis(
                stk[:, :, field], idx[:, None], axis=1)[:, 0]

        # taken jump: pop every frame whose body the jump escaped
        pop_lsp = lsp
        for _ in range(depth):
            cond = (pop_lsp > 0) & (top(1, lstack, pop_lsp) < pc_new)
            pop_lsp = jnp.where(cond, pop_lsp - 1, pop_lsp)

        # normal advance: iterate / pop frames whose body just ended
        it_stack, it_lsp, it_pcn = lstack, lsp, pc_new
        done = jnp.zeros(B, bool)
        for _ in range(depth):
            idx = jnp.clip(it_lsp - 1, 0, depth - 1)
            t_end = top(1, it_stack, it_lsp)
            cond = (~done) & (it_lsp > 0) & (it_pcn == t_end + 1)
            rem = top(2, it_stack, it_lsp) - 1
            cont = rem > 0
            set_m = cond & cont
            upd2 = (lane8 == idx[:, None]) & set_m[:, None]
            it_stack = jnp.where(
                upd2[:, :, None]
                & (jnp.arange(3) == 2)[None, None, :],
                rem[:, None, None], it_stack)
            it_pcn = jnp.where(set_m, top(0, it_stack, it_lsp), it_pcn)
            it_lsp = jnp.where(cond & ~cont, it_lsp - 1, it_lsp)
            done = done | set_m

        is_jtaken = ctrl == 1
        fix = active & ~is_ret & ~flt
        pc = jnp.where(fix, jnp.where(is_jtaken, pc_new, it_pcn), s.pc)
        lsp_f = jnp.where(fix, jnp.where(is_jtaken, pop_lsp, it_lsp),
                          jnp.where(active, lsp, s.lsp))
        lstack_f = jnp.where(
            fix[:, None, None],
            jnp.where(is_jtaken[:, None, None], lstack, it_stack),
            jnp.where(active[:, None, None], lstack, s.lstack))

        # --- merge, masking out inactive lanes -----------------------
        regs = jnp.where(active[:, None], regs, s.regs)
        fault = s.fault
        if protect:
            fault = jnp.where(flt[:, None], frows, s.fault)
        s2 = ReqState(
            pc=pc, regs=regs, lstack=lstack_f, lsp=lsp_f,
            inflight=jnp.where(active, inflight, s.inflight),
            halted=jnp.where(active, halted, s.halted),
            ret=jnp.where(active, ret, s.ret),
            status=jnp.where(active, status, s.status),
            steps=s.steps + active.astype(jnp.int64),
            ctrl=jnp.where(active, ctrl, s.ctrl),
            pc_new=jnp.where(active, pc_new, s.pc_new),
            fault=fault)
        return s2, mem

    return vector_step, lane_intervals


def _program_statics(codes, fuels):
    """Normalize a merged instruction store: per-slot entry/end/fuel
    vectors plus the static memcpy window — shared by the dense and
    sharded engine builders."""
    codes = [np.asarray(c, dtype=np.int64).reshape(-1, isa.INSTR_WIDTH)
             for c in codes]
    if not codes:
        raise ValueError("engine needs at least one program")
    code_np = np.concatenate(codes, axis=0)
    lens_np = np.asarray([c.shape[0] for c in codes], dtype=np.int64)
    start_np = np.concatenate(
        [np.zeros(1, np.int64), np.cumsum(lens_np)[:-1]])
    end_np = start_np + lens_np
    fuel_np = np.asarray([int(f) for f in fuels], dtype=np.int64)
    if fuel_np.shape != (len(codes),):
        raise ValueError("one step bound per program required")
    # Static memcpy window: the largest cap used by any merged program.
    memcpy_caps = [int(r[isa.F_IMM]) for r in code_np
                   if int(r[isa.F_OP]) == int(Op.MEMCPY)]
    max_window = int(min(max(memcpy_caps, default=1), isa.MAX_MEMCPY_WORDS))
    return code_np, start_np, end_np, fuel_np, max_window


def _build_engine(codes: Sequence[np.ndarray], fuels: Sequence[int],
                  regions: RegionTable, n_devices: int, batch: int,
                  protect: bool = True,
                  static_noconflict: bool = False):
    """Build the lockstep engine over a *merged* instruction store.

    ``codes`` holds one program per dispatch-table slot, laid out back to
    back in slot order — the same layout as the registry's shared BRAM
    instruction store, so slot ``i`` starts at the registry's
    ``start_pc[i]``.  Returns jit-compiled
    ``f(mem, params, homes, failed, op_sel) -> VMResult`` where
    ``op_sel``: int64[batch] picks each request's program; the request
    starts at its program's first pc and terminates against its own
    program end and verified step bound (``fuels[op_sel[b]]``).

    ``mem``: int64[n_devices, pool_words] shared by the whole batch;
    ``params``: int64[batch, <=8]; ``homes``: int64[batch] per-request
    executing-host ids; ``failed``: bool[n_devices].  Result fields
    ``ret/status/steps`` are [batch] and ``regs`` is [batch, 16].
    Call under ``vm.x64()`` (or use the ``invoke*`` wrappers).

    ``static_noconflict=True`` builds the engine *without* the per-step
    sweep-line conflict check: every macro-step takes the vectorized
    path (the serialized branch stays compiled in behind a never-true
    predicate — see the note in ``step`` — but the interval
    computation and sort are gone).  The caller must hold a
    registration-time proof (``access.prove_wave_noconflict``) that no
    macro-step of any wave run on this engine can conflict; the engine
    trusts the flag.  Top-footprint waves keep the default build — the
    sweep is the verbatim fallback.
    """
    code_np, start_np, end_np, fuel_np, max_window = \
        _program_statics(codes, fuels)
    n_ops = int(fuel_np.shape[0])
    n_instr = int(code_np.shape[0])
    base_np, mask_np, _ = regions.as_arrays()
    n_regions = int(base_np.shape[0])
    n_dev = int(n_devices)
    B = int(batch)
    depth = isa.LOOP_STACK_DEPTH

    def run(mem, params, homes, failed, op_sel):
        code = jnp.asarray(code_np)
        base_c = jnp.asarray(base_np)
        mask_c = jnp.asarray(mask_np)
        mem = jnp.asarray(mem, jnp.int64)
        homes = jnp.asarray(homes, jnp.int64).reshape(B)
        failed = jnp.asarray(failed, jnp.bool_)
        op_sel = jnp.clip(jnp.asarray(op_sel, jnp.int64).reshape(B),
                          0, n_ops - 1)
        # per-request dispatch: entry pc, program end, and step-bound fuel
        pc0 = jnp.asarray(start_np)[op_sel]
        end_arr = jnp.asarray(end_np)[op_sel]
        fuel_arr = jnp.asarray(fuel_np)[op_sel]
        pool_words = mem.shape[1]

        regs0 = jnp.zeros((B, isa.NUM_REGS), jnp.int64)
        params = jnp.asarray(params, jnp.int64).reshape(B, -1)
        if params.shape[1]:
            regs0 = lax.dynamic_update_slice(regs0, params, (0, 0))

        ops = _DenseOps(n_dev, int(pool_words))
        step_one = _make_scalar_step(
            base_c=base_c, mask_c=mask_c, failed=failed, n_dev=n_dev,
            max_window=max_window, depth=depth, ops=ops, protect=protect)
        serial_step = _serial_step_fn(step_one)
        vector_step, lane_intervals = _make_vector_step(
            base_c=base_c, mask_c=mask_c, n_regions=n_regions,
            n_dev=n_dev, pool_words=int(pool_words),
            max_window=max_window, depth=depth, B=B, homes=homes,
            failed=failed, ops=ops, protect=protect)

        def live_mask(s: ReqState):
            return (~s.halted) & (s.pc < end_arr) & (s.steps < fuel_arr)

        def step(carry):
            s, mem = carry
            active = live_mask(s)
            rows = code[jnp.clip(s.pc, 0, n_instr - 1)]
            if B == 1:
                # single request: the scalar switch interpreter, no
                # conflict machinery — the classic Tiara MP datapath
                s2, mem2 = serial_step(s, mem, rows, homes, active)
            elif static_noconflict:
                # statically proven conflict-free: the per-step sweep
                # (lane_intervals + interval sort) is gone.  The cond
                # and its serialized branch stay: XLA CPU outlines cond
                # branches into their own computations, and inlining
                # vector_step into the while body instead measures ~2x
                # slower at B=1024 (fusion boundaries vanish).  The
                # predicate can never fire (pc is clipped non-negative),
                # and if it somehow did, serial_step is semantically
                # correct — it is the conservative fallback.
                s2, mem2 = lax.cond(
                    jnp.any(s.pc < -1),
                    lambda s_, m_, r_, a_: serial_step(s_, m_, r_, homes,
                                                       a_),
                    vector_step, s, mem, rows, active)
            else:
                s2, mem2 = lax.cond(
                    _sweep_conflict(*lane_intervals(s, rows, active)),
                    lambda s_, m_, r_, a_: serial_step(s_, m_, r_, homes,
                                                       a_),
                    vector_step, s, mem, rows, active)
            return s2, mem2

        def cond(carry):
            s, _ = carry
            return jnp.any(live_mask(s))

        init = ReqState(
            pc=pc0, regs=regs0,
            lstack=jnp.zeros((B, depth, 3), jnp.int64),
            lsp=jnp.zeros(B, jnp.int64),
            inflight=jnp.zeros(B, jnp.int64), halted=jnp.zeros(B, bool),
            ret=jnp.zeros(B, jnp.int64),
            status=jnp.full(B, isa.STATUS_FELL_OFF, jnp.int64),
            steps=jnp.zeros(B, jnp.int64),
            ctrl=jnp.zeros(B, jnp.int64), pc_new=jnp.zeros(B, jnp.int64),
            fault=jnp.tile(jnp.asarray(NO_FAULT), (B, 1)))

        final, mem_f = lax.while_loop(cond, step, (init, mem))
        status = jnp.where(
            final.halted, final.status,
            jnp.where(final.steps >= fuel_arr, _i64(isa.STATUS_FUEL),
                      _i64(isa.STATUS_FELL_OFF)))
        return VMResult(mem=mem_f, ret=final.ret, status=status,
                        steps=final.steps, regs=final.regs,
                        fault=final.fault)

    return jax.jit(run)


def _build_sharded_engine(codes: Sequence[np.ndarray], fuels: Sequence[int],
                          regions: RegionTable, n_devices: int,
                          batch_per_device: int, axis: str = "pool",
                          protect: bool = True,
                          static_noconflict: bool = False):
    """Build the mesh-sharded lockstep engine: the pool's leading
    ``n_devices`` axis is sharded over a 1-D device mesh (``shard_map``),
    each device executes the home-bucketed sub-wave it owns, and remote
    LOAD/MEMCPY/STORE traffic lowers to collectives across the mesh axis
    (``all_gather`` the requests, owning shards answer, ``psum`` routes
    the words back — see :class:`_ShardOps`).

    Semantics are *identical* to the dense mixed engine run over the
    same wave in arrival order: macro-steps stay in lockstep across the
    mesh (the driver condition and the conflict predicate are globally
    agreed each step), conflict-free steps vectorize per device, and a
    contended macro-step falls back to a replicated serialized scan in
    **global arrival order** — the home-bucketed wave order is not the
    arrival order, so each lane carries its arrival rank and the
    fallback sorts by it.  That is what lets deterministic round-robin
    STORE/CAS contention survive sharding bit-for-bit.

    Returns jit-compiled
    ``f(mem, params, homes, failed, op_sel, arrival) -> VMResult`` with
    device-major fields: ``mem`` is ``(n_devices, pool_words)``,
    ``ret/status/steps`` are ``(n_devices, batch_per_device)`` and
    ``regs`` is ``(n_devices, batch_per_device, 16)``.  Lanes with
    ``op_sel < 0`` are padding (sub-waves are ragged) and start halted.
    Call under ``vm.x64()`` (or use :func:`invoke_sharded_mixed`).
    """
    from jax.sharding import PartitionSpec as _P

    from repro.core.memory import make_device_mesh

    code_np, start_np, end_np, fuel_np, max_window = \
        _program_statics(codes, fuels)
    n_ops = int(fuel_np.shape[0])
    n_instr = int(code_np.shape[0])
    base_np, mask_np, _ = regions.as_arrays()
    n_regions = int(base_np.shape[0])
    n_dev = int(n_devices)
    Bp = int(batch_per_device)
    N = n_dev * Bp
    depth = isa.LOOP_STACK_DEPTH
    mesh = make_device_mesh(n_dev, axis)

    def device_body(mem, params, homes, failed, op_sel, arrival):
        # per-device shards: mem (1, P); params (1, Bp, w); homes /
        # op_sel / arrival (1, Bp); failed (n_devices,) replicated
        me = lax.axis_index(axis)
        code = jnp.asarray(code_np)
        base_c = jnp.asarray(base_np)
        mask_c = jnp.asarray(mask_np)
        shard = jnp.asarray(mem, jnp.int64)[0]
        pool_words = shard.shape[0]
        homes_l = jnp.asarray(homes, jnp.int64).reshape(Bp)
        failed = jnp.asarray(failed, jnp.bool_)
        op_sel_l = jnp.asarray(op_sel, jnp.int64).reshape(Bp)
        arrival_l = jnp.asarray(arrival, jnp.int64).reshape(Bp)
        pad = op_sel_l < 0
        sel = jnp.clip(op_sel_l, 0, n_ops - 1)
        pc0 = jnp.asarray(start_np)[sel]
        end_arr = jnp.asarray(end_np)[sel]
        fuel_arr = jnp.asarray(fuel_np)[sel]
        params_l = jnp.asarray(params, jnp.int64).reshape(Bp, -1)
        regs0 = jnp.zeros((Bp, isa.NUM_REGS), jnp.int64)
        if params_l.shape[1]:
            regs0 = lax.dynamic_update_slice(regs0, params_l, (0, 0))

        ops = _ShardOps(n_dev, int(pool_words), axis, me)
        step_one = _make_scalar_step(
            base_c=base_c, mask_c=mask_c, failed=failed, n_dev=n_dev,
            max_window=max_window, depth=depth, ops=ops, protect=protect)
        vector_step, lane_intervals = _make_vector_step(
            base_c=base_c, mask_c=mask_c, n_regions=n_regions,
            n_dev=n_dev, pool_words=int(pool_words),
            max_window=max_window, depth=depth, B=Bp, homes=homes_l,
            failed=failed, ops=ops, protect=protect)

        def gather(x):
            return lax.all_gather(x, axis).reshape((N,) + x.shape[1:])

        def serial_macro(s, mem, rows, active):
            # Contended macro-step: replicate the whole wave's state on
            # every device and serialize in GLOBAL ARRIVAL order (the
            # home-bucketed wave order is not arrival order).  Register
            # state stays replicated through the scan — reads are
            # psum-routed, so every device computes identical values —
            # and each device applies only its own shard's writes.
            s_all = jax.tree_util.tree_map(gather, s)
            rows_all = gather(rows)
            act_all = gather(active)
            homes_all = gather(homes_l)
            perm = jnp.argsort(gather(arrival_l))

            s_p = jax.tree_util.tree_map(lambda x: x[perm], s_all)

            def body(mem, x):
                s1, row, home, act = x
                s2, mem2 = step_one(s1, mem, row, home, act)
                return mem2, s2

            mem2, s_scan = lax.scan(
                body, mem,
                (s_p, rows_all[perm], homes_all[perm], act_all[perm]))

            def unperm(y):
                return jnp.zeros_like(y).at[perm].set(y)

            s_out = jax.tree_util.tree_map(unperm, s_scan)
            s_mine = jax.tree_util.tree_map(
                lambda x: lax.dynamic_slice_in_dim(x, me * Bp, Bp, 0),
                s_out)
            return s_mine, mem2

        def live_mask(s: ReqState):
            return (~s.halted) & (s.pc < end_arr) & (s.steps < fuel_arr)

        def step(carry):
            s, mem = carry
            active = live_mask(s)
            rows = code[jnp.clip(s.pc, 0, n_instr - 1)]
            if static_noconflict:
                # statically proven conflict-free: skip both the
                # footprint all_gather (a collective per macro-step)
                # and the sweep.  The cond + serial branch stay (same
                # reason as the dense engine: the XLA CPU backend keeps
                # cond branches outlined, and inlining vector_step into
                # the while body compiles measurably worse); the
                # predicate is device-local and identically false on
                # every shard, so the branch-agreement requirement for
                # the collectives inside serial_macro still holds
                return lax.cond(jnp.any(s.pc < -1), serial_macro,
                                vector_step, s, mem, rows, active)
            # conflict existence is a GLOBAL question: gather every
            # device's footprint intervals before the sweep, so all
            # devices agree on the branch (divergence would deadlock
            # the collectives inside)
            iv = lax.all_gather(
                jnp.stack(lane_intervals(s, rows, active)), axis)
            m = jnp.moveaxis(iv, 1, 0).reshape(4, -1)
            conflict = _sweep_conflict(m[0], m[1], m[2], m[3])
            return lax.cond(conflict, serial_macro, vector_step,
                            s, mem, rows, active)

        def cond(carry):
            s, _ = carry
            live = jnp.any(live_mask(s)).astype(jnp.int32)
            return lax.psum(live, axis) > 0

        init = ReqState(
            pc=pc0, regs=regs0,
            lstack=jnp.zeros((Bp, depth, 3), jnp.int64),
            lsp=jnp.zeros(Bp, jnp.int64),
            inflight=jnp.zeros(Bp, jnp.int64),
            halted=pad,                       # padding lanes never run
            ret=jnp.zeros(Bp, jnp.int64),
            status=jnp.full(Bp, isa.STATUS_FELL_OFF, jnp.int64),
            steps=jnp.zeros(Bp, jnp.int64),
            ctrl=jnp.zeros(Bp, jnp.int64),
            pc_new=jnp.zeros(Bp, jnp.int64),
            fault=jnp.tile(jnp.asarray(NO_FAULT), (Bp, 1)))

        final, mem_f = lax.while_loop(cond, step, (init, shard))
        status = jnp.where(
            final.halted, final.status,
            jnp.where(final.steps >= fuel_arr, _i64(isa.STATUS_FUEL),
                      _i64(isa.STATUS_FELL_OFF)))
        return VMResult(mem=mem_f[None, :], ret=final.ret[None],
                        status=status[None], steps=final.steps[None],
                        regs=final.regs[None], fault=final.fault[None])

    # replication checking off: it rejects collectives under lax.cond
    # even with a replicated predicate — the conflict-fallback shape
    sharded = jax.shard_map(
        device_body, mesh=mesh, check_vma=False,
        in_specs=(_P(axis, None), _P(axis, None, None), _P(axis, None),
                  _P(None), _P(axis, None), _P(axis, None)),
        out_specs=VMResult(mem=_P(axis, None), ret=_P(axis, None),
                           status=_P(axis, None), steps=_P(axis, None),
                           regs=_P(axis, None, None),
                           fault=_P(axis, None, None)))
    return jax.jit(sharded)


def build_batched_vm(op: VerifiedOperator, regions: RegionTable,
                     n_devices: int, batch: int, protect: bool = True,
                     static_noconflict: bool = False):
    """Returns jit-compiled ``f(mem, params, homes, failed) -> VMResult`` —
    the one-program specialization of :func:`_build_engine` (its merged
    store holds a single program and every request dispatches to slot 0).
    Call under ``vm.x64()`` (or use :func:`invoke` / :func:`invoke_batched`).
    """
    eng = _build_engine([op.code], [op.step_bound], regions, n_devices,
                        batch, protect=protect,
                        static_noconflict=static_noconflict)
    sel0 = np.zeros(int(batch), dtype=np.int64)

    def run(mem, params, homes, failed):
        return eng(mem, params, homes, failed, sel0)

    return run


def build_mixed_batched_vm(ops: Sequence[VerifiedOperator],
                           regions: RegionTable, n_devices: int,
                           batch: int, protect: bool = True,
                           static_noconflict: bool = False):
    """The multi-tenant engine: one lockstep launch executing a batch of
    requests whose per-request ``op_sel`` picks among the ``ops`` programs
    (laid out back to back like the registry's instruction store, so
    ``op_sel`` is exactly the registry ``op_id`` when ``ops`` lists every
    slot in op_id order).  Returns jit-compiled
    ``f(mem, params, homes, failed, op_sel) -> VMResult``."""
    return _build_engine([o.code for o in ops],
                         [o.step_bound for o in ops],
                         regions, n_devices, batch, protect=protect,
                         static_noconflict=static_noconflict)


def build_sharded_mixed_vm(ops: Sequence[VerifiedOperator],
                           regions: RegionTable, n_devices: int,
                           batch_per_device: int, axis: str = "pool",
                           protect: bool = True,
                           static_noconflict: bool = False):
    """The pod-scale engine: the pool's leading axis sharded over a 1-D
    device mesh, one home-bucketed sub-wave per device, cross-device
    LOAD/MEMCPY lowered to collectives (see :func:`_build_sharded_engine`
    for the semantics contract).  Returns jit-compiled
    ``f(mem, params, homes, failed, op_sel, arrival) -> VMResult`` with
    device-major ``(n_devices, batch_per_device)`` result fields."""
    return _build_sharded_engine([o.code for o in ops],
                                 [o.step_bound for o in ops],
                                 regions, n_devices, batch_per_device,
                                 axis, protect=protect,
                                 static_noconflict=static_noconflict)


def build_vm(op: VerifiedOperator, regions: RegionTable, n_devices: int,
             protect: bool = True):
    """Single-request entry point: ``f(mem, params, home, failed)`` —
    the ``batch=1`` specialization of :func:`build_batched_vm` with scalar
    result fields, kept for every existing caller."""
    batched = build_batched_vm(op, regions, n_devices, batch=1,
                               protect=protect)

    def run(mem, params, home, failed):
        params = jnp.asarray(params, jnp.int64).reshape(1, -1)
        homes = jnp.asarray(home, jnp.int64).reshape(1)
        out = batched(mem, params, homes, failed)
        return VMResult(mem=out.mem, ret=out.ret[0], status=out.status[0],
                        steps=out.steps[0], regs=out.regs[0],
                        fault=out.fault[0])

    return run


# Serializing a program's code for its cache key costs tobytes() over the
# whole instruction array; a registry hot path keys the full merged store
# per wave, so memoize per live VerifiedOperator.  Keyed by id() but
# guarded by a weakref identity check — recycled ids miss and recompute,
# and dead entries are purged on the weakref callback.
_CODE_BYTES_MEMO: Dict[int, Tuple[object, bytes]] = {}


def _code_bytes(op: VerifiedOperator) -> bytes:
    ent = _CODE_BYTES_MEMO.get(id(op))
    if ent is not None and ent[0]() is op:
        return ent[1]
    key = id(op)
    b = op.code.tobytes()
    _CODE_BYTES_MEMO[key] = (
        weakref.ref(op, lambda _: _CODE_BYTES_MEMO.pop(key, None)), b)
    return b


def engine_key(op: VerifiedOperator, regions: RegionTable, n_dev: int,
               batch: int, *extra) -> Tuple:
    """Content-addressed cache key for a built engine (object ids recycle
    after GC — never key on id).  Shared with the compiled-path cache."""
    base, mask, _ = regions.as_arrays()
    return (_code_bytes(op), base.tobytes(), mask.tobytes(),
            op.step_bound, n_dev, batch) + extra


def mixed_engine_key(ops: Sequence[VerifiedOperator], regions: RegionTable,
                     n_dev: int, batch: int, *extra) -> Tuple:
    """Content-addressed cache key for a mixed (multi-program) engine."""
    base, mask, _ = regions.as_arrays()
    return (tuple((_code_bytes(o), int(o.step_bound)) for o in ops),
            base.tobytes(), mask.tobytes(), n_dev, batch) + extra


# Engines are cached per (operator, regions, n_devices, batch): a serving
# loop should pad request waves to a few fixed batch sizes (e.g. powers of
# two) so the cache stays small — every new B is a fresh XLA compile.
_VM_CACHE: Dict[Tuple, object] = {}


def engine_cached(op: VerifiedOperator, regions: RegionTable, n_dev: int,
                  batch: int, protect: bool = True,
                  static_noconflict: bool = False) -> bool:
    """True iff the batched interpreter engine for this (op, batch) is
    already built — a cache miss costs an XLA compile, which the
    dispatch cost model charges for."""
    return engine_key(op, regions, n_dev, batch, bool(protect),
                      bool(static_noconflict)) in _VM_CACHE


def mixed_engine_cached(ops: Sequence[VerifiedOperator],
                        regions: RegionTable, n_dev: int,
                        batch: int, protect: bool = True,
                        static_noconflict: bool = False) -> bool:
    return mixed_engine_key(ops, regions, n_dev, batch, bool(protect),
                            bool(static_noconflict)) in _VM_CACHE


def _cached_engine(op: VerifiedOperator, regions: RegionTable, n_dev: int,
                   batch: int, protect: bool = True,
                   static_noconflict: bool = False):
    key = engine_key(op, regions, n_dev, batch, bool(protect),
                     bool(static_noconflict))
    fn = _VM_CACHE.get(key)
    if fn is None:
        fn = build_batched_vm(op, regions, n_dev, batch, protect=protect,
                              static_noconflict=static_noconflict)
        _VM_CACHE[key] = fn
    return fn


def _cached_mixed_engine(ops: Sequence[VerifiedOperator],
                         regions: RegionTable, n_dev: int, batch: int,
                         protect: bool = True,
                         static_noconflict: bool = False):
    key = mixed_engine_key(ops, regions, n_dev, batch, bool(protect),
                           bool(static_noconflict))
    fn = _VM_CACHE.get(key)
    if fn is None:
        fn = build_mixed_batched_vm(ops, regions, n_dev, batch,
                                    protect=protect,
                                    static_noconflict=static_noconflict)
        _VM_CACHE[key] = fn
    return fn


def _sharded_engine_key(ops: Sequence[VerifiedOperator],
                        regions: RegionTable, n_dev: int,
                        batch_per_device: int, axis: str,
                        protect: bool = True,
                        static_noconflict: bool = False) -> Tuple:
    import jax as _jax
    dev_ids = tuple(d.id for d in _jax.devices()[:n_dev])
    return mixed_engine_key(ops, regions, n_dev, batch_per_device,
                            "sharded", axis, dev_ids, bool(protect),
                            bool(static_noconflict))


def sharded_engine_cached(ops: Sequence[VerifiedOperator],
                          regions: RegionTable, n_dev: int,
                          batch_per_device: int,
                          axis: str = "pool",
                          protect: bool = True,
                          static_noconflict: bool = False) -> bool:
    """True iff the sharded mesh engine for this (ops, sub-wave size) is
    already built — a miss costs an XLA compile of the whole shard_map
    program, which the dispatch cost model charges for."""
    return _sharded_engine_key(ops, regions, n_dev, batch_per_device,
                               axis, protect, static_noconflict) in _VM_CACHE


def _cached_sharded_engine(ops: Sequence[VerifiedOperator],
                           regions: RegionTable, n_dev: int,
                           batch_per_device: int, axis: str = "pool",
                           protect: bool = True,
                           static_noconflict: bool = False):
    key = _sharded_engine_key(ops, regions, n_dev, batch_per_device, axis,
                              protect, static_noconflict)
    fn = _VM_CACHE.get(key)
    if fn is None:
        fn = build_sharded_mixed_vm(ops, regions, n_dev, batch_per_device,
                                    axis, protect=protect,
                                    static_noconflict=static_noconflict)
        _VM_CACHE[key] = fn
    return fn


def run_batched_fn(fn, mem: np.ndarray, p: np.ndarray, h: np.ndarray,
                   failed: Optional[Set[int]], *,
                   block: bool = True) -> "BatchedInvokeResult":
    """Execute a built batched engine: numpy in, numpy out, x64 handled.
    Shared by the interpreter and compiled wrappers.

    With ``block=False`` the result fields are left as device arrays —
    XLA's async dispatch keeps computing while the caller goes on
    posting more work (the endpoint's split-phase doorbell); call
    :func:`materialize_result` to retire the per-lane fields to numpy
    (the pool stays on the device).  The launch itself (tracing,
    validation, cache lookup) still happens eagerly, so malformed
    waves raise here either way."""
    n_dev = int(mem.shape[0])
    with x64():
        out = fn(jnp.asarray(mem, jnp.int64), jnp.asarray(p),
                 jnp.asarray(h), jnp.asarray(_failed_mask(n_dev, failed)))
        if block:
            out = jax.tree_util.tree_map(np.asarray, out)
    return BatchedInvokeResult(mem=out.mem, ret=out.ret, status=out.status,
                               steps=out.steps, regs=out.regs,
                               fault=out.fault)


def materialize_result(res: "BatchedInvokeResult") -> "BatchedInvokeResult":
    """Retire a (possibly deferred) batched result: the per-lane fields
    (``ret``, ``status``, ``steps``, ``regs``, ``fault``) come to host
    numpy, and ``mem`` is left where it is — a device array, or a
    future still computing.  Blocks until the launch that produced the
    per-lane fields completes; a no-op on an already-materialized
    result.  The pool crosses to the host only when a caller asks for
    it (the endpoint's control path)."""
    ret, status, steps, regs, fault = jax.device_get(
        (res.ret, res.status, res.steps, res.regs, res.fault))
    return BatchedInvokeResult(
        mem=res.mem, ret=np.asarray(ret), status=np.asarray(status),
        steps=np.asarray(steps), regs=np.asarray(regs),
        fault=np.asarray(fault))


def result_ready(res: "BatchedInvokeResult") -> bool:
    """Non-blocking readiness probe of a deferred batched result: True
    once every per-lane field's device computation has landed (numpy
    fields are trivially ready; jax arrays without ``is_ready`` report
    ready and the subsequent materialization simply blocks).  The pool
    is not probed: retirement does not bring it back."""
    for f in (res.ret, res.status, res.steps, res.regs, res.fault):
        probe = getattr(f, "is_ready", None)
        if probe is not None and not probe():
            return False
    return True


@functools.partial(jax.jit, static_argnames="count")
def _pool_slice(mem, row, start, *, count):
    return lax.dynamic_slice(mem, (row, start), (1, count))[0]


def pool_words(mem, device: int, start: int, count: int) -> np.ndarray:
    """Words ``[start, start + count)`` of pool row ``device`` as host
    int64.  A host pool is sliced in place (a view); a device pool — or
    a future still computing, which orders the read after the wave that
    produces it — is sliced where it lives, so only ``count`` words
    cross to the host.  One compile per ``count``: the offsets are
    traced."""
    if not 0 <= device < mem.shape[0]:
        # a traced row index would be clamped, not refused
        raise IndexError(f"pool row {device} out of range "
                         f"[0, {mem.shape[0]})")
    if isinstance(mem, np.ndarray):
        return mem[device, start:start + count]
    with x64():
        return np.asarray(_pool_slice(mem, np.int64(device),
                                      np.int64(start), count=int(count)))


def _wrap_param(v) -> np.int64:
    return np.int64(np.uint64(v & (2**64 - 1)).astype(np.uint64)
                    .view(np.int64)) \
        if v > 2**63 - 1 or v < -2**63 else np.int64(v)


def _failed_mask(n_dev: int, failed: Optional[Set[int]]) -> np.ndarray:
    m = np.zeros(n_dev, dtype=bool)
    for f in (failed or ()):
        m[f] = True
    return m


def homes_array(homes: Union[int, Sequence[int]],
                batch: int) -> np.ndarray:
    """Normalize a ``homes`` argument (scalar broadcast or per-request
    sequence) to i64[batch] — the one place that marshalling lives."""
    h = np.full(batch, homes, dtype=np.int64) if np.isscalar(homes) \
        else np.asarray(list(homes), dtype=np.int64)
    if h.shape != (batch,):
        raise ValueError(f"homes shape {h.shape} != ({batch},)")
    return h


def _marshal_batch(params: Sequence[Sequence[int]],
                   homes: Union[int, Sequence[int]]
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Validate and pack a request batch: params -> i64[B, width],
    homes -> i64[B].  Shared by the interpreter and compiled wrappers."""
    batch = len(params)
    if batch == 0:
        raise ValueError("empty request batch")
    width = max(max((len(row) for row in params), default=0), 1)
    p = np.zeros((batch, width), dtype=np.int64)
    for b, row in enumerate(params):
        for i, v in enumerate(row):
            p[b, i] = _wrap_param(v)
    return p, homes_array(homes, batch)


def invoke(op: VerifiedOperator, regions: RegionTable, mem: np.ndarray,
           params: Sequence[int] = (), *, home: int = 0,
           failed: Optional[Set[int]] = None,
           protect: bool = True) -> "InvokeResult":
    """Convenience entry point: numpy in, numpy out, x64 handled."""
    n_dev = int(mem.shape[0])
    with x64():
        fn = _cached_engine(op, regions, n_dev, batch=1, protect=protect)
        p = np.zeros((1, max(len(params), 1)), dtype=np.int64)
        for i, v in enumerate(params):
            p[0, i] = _wrap_param(v)
        out = fn(jnp.asarray(mem, jnp.int64), jnp.asarray(p),
                 jnp.asarray([home], jnp.int64),
                 jnp.asarray(_failed_mask(n_dev, failed)))
        out = jax.tree_util.tree_map(np.asarray, out)
    return InvokeResult(mem=out.mem, ret=int(out.ret[0]),
                        status=int(out.status[0]), steps=int(out.steps[0]),
                        regs=out.regs[0], fault=fault_info(out.fault[0]))


def invoke_batched(op: VerifiedOperator, regions: RegionTable,
                   mem: np.ndarray, params: Sequence[Sequence[int]],
                   *, homes: Union[int, Sequence[int]] = 0,
                   failed: Optional[Set[int]] = None,
                   block: bool = True,
                   protect: bool = True,
                   static_noconflict: bool = False) -> "BatchedInvokeResult":
    """Run a batch of requests against one shared pool: numpy in/out.

    ``params`` is a [B][k] nested sequence (one row per request); ``homes``
    is a scalar (all requests from the same host) or a [B] sequence.
    ``block=False`` defers retirement (see :func:`run_batched_fn`).
    ``static_noconflict=True`` asserts the caller holds a registration-time
    proof that the wave is conflict-free; the engine then skips the
    per-step runtime sweep (see :func:`_build_engine`).
    """
    p, h = _marshal_batch(params, homes)
    fn = _cached_engine(op, regions, int(mem.shape[0]), p.shape[0],
                        protect=protect,
                        static_noconflict=static_noconflict)
    return run_batched_fn(fn, mem, p, h, failed, block=block)


def invoke_batched_mixed(ops: Sequence[VerifiedOperator],
                         regions: RegionTable, mem: np.ndarray,
                         op_sel: Sequence[int],
                         params: Sequence[Sequence[int]], *,
                         homes: Union[int, Sequence[int]] = 0,
                         failed: Optional[Set[int]] = None,
                         block: bool = True,
                         protect: bool = True,
                         static_noconflict: bool = False
                         ) -> "BatchedInvokeResult":
    """Run a *mixed* batch — request ``b`` executes ``ops[op_sel[b]]`` —
    against one shared pool in one lockstep launch: numpy in/out.

    Semantics are the engine's deterministic round-robin interleaving
    across programs: each macro-step, request ``i`` executes the next
    instruction *of its own operator* and observes all same-step memory
    effects of requests ``j < i``.  ``block=False`` defers retirement
    (see :func:`run_batched_fn`).  ``static_noconflict=True`` asserts a
    registration-time proof that the wave is conflict-free; the engine
    then skips the per-step runtime sweep (see :func:`_build_engine`).
    """
    p, h = _marshal_batch(params, homes)
    B = p.shape[0]
    sel = np.asarray(list(op_sel), dtype=np.int64)
    if sel.shape != (B,):
        raise ValueError(f"op_sel shape {sel.shape} != ({B},)")
    if sel.size and (sel.min() < 0 or sel.max() >= len(ops)):
        raise ValueError(
            f"op_sel entries must be in [0, {len(ops)}) for {len(ops)} "
            f"programs; got range [{sel.min()}, {sel.max()}]")
    eng = _cached_mixed_engine(tuple(ops), regions, int(mem.shape[0]), B,
                               protect=protect,
                               static_noconflict=static_noconflict)

    def fn(mem_j, p_j, h_j, failed_j):
        return eng(mem_j, p_j, h_j, failed_j, sel)

    return run_batched_fn(fn, mem, p, h, failed, block=block)


def invoke_sharded_mixed(ops: Sequence[VerifiedOperator],
                         regions: RegionTable, mem: np.ndarray,
                         plan, params: Sequence[Sequence[int]], *,
                         failed: Optional[Set[int]] = None,
                         axis: str = "pool",
                         protect: bool = True,
                         static_noconflict: bool = False
                         ) -> "BatchedInvokeResult":
    """Run a mixed wave on the mesh-sharded engine: numpy in/out.
    ``static_noconflict=True`` asserts a registration-time conflict proof;
    the sharded engine then skips both the per-step footprint all_gather
    and the sweep (see :func:`_build_sharded_engine`).

    ``plan`` is a home-bucketed :class:`~repro.core.compile.MixedPlan`
    (built with ``plan_mixed_batch(op_ids, homes=..., n_devices=...)``):
    its ``order`` lays the wave out device-major, each device's ragged
    sub-wave is padded to ``plan.batch_per_device`` lanes, and results
    scatter back to arrival order through the same permutation.  The
    result is bit-identical to :func:`invoke_batched_mixed` over the
    arrival-order wave (contended STORE/CAS included — the engine's
    serialized fallback sorts by arrival rank)."""
    if getattr(plan, "device_counts", None) is None:
        raise ValueError(
            "plan carries no device placement; build it with "
            "plan_mixed_batch(op_ids, homes=..., n_devices=...)")
    n_dev = int(mem.shape[0])
    if plan.n_devices != n_dev:
        raise ValueError(
            f"plan places {plan.n_devices} devices but the pool has "
            f"{n_dev} rows")
    p, h = _marshal_batch(params, plan.homes)
    B = plan.batch
    if p.shape[0] != B:
        raise ValueError(f"{p.shape[0]} param rows for a {B}-request plan")
    Bp = int(plan.batch_per_device)
    width = p.shape[1]
    # device-major marshal: plan.order is home-bucketed, so device d's
    # sub-wave is one contiguous slice of the sorted batch; pad lanes
    # carry op_sel = -1 (start halted) and arrival ranks past the wave
    sel = np.full((n_dev, Bp), -1, dtype=np.int64)
    pz = np.zeros((n_dev, Bp, width), dtype=np.int64)
    hz = np.zeros((n_dev, Bp), dtype=np.int64)
    az = np.full((n_dev, Bp), B, dtype=np.int64)
    pos = 0
    for d in range(n_dev):
        c = int(plan.device_counts[d])
        lanes = plan.order[pos:pos + c]
        sel[d, :c] = plan.op_ids[lanes]
        pz[d, :c] = p[lanes]
        hz[d, :c] = h[lanes]
        hz[d, c:] = d
        az[d, :c] = lanes            # arrival rank = arrival index
        pos += c
    eng = _cached_sharded_engine(tuple(ops), regions, n_dev, Bp, axis,
                                 protect=protect,
                                 static_noconflict=static_noconflict)
    from repro.core import memory as _memory
    with x64():
        mem_dev = _memory.shard_pool(np.asarray(mem, dtype=np.int64),
                                     axis=axis) \
            if n_dev > 1 else jnp.asarray(mem, jnp.int64)
        out = eng(mem_dev, jnp.asarray(pz), jnp.asarray(hz),
                  jnp.asarray(_failed_mask(n_dev, failed)),
                  jnp.asarray(sel), jnp.asarray(az))
        out = jax.tree_util.tree_map(np.asarray, out)
    ret = np.zeros(B, dtype=np.int64)
    status = np.zeros(B, dtype=np.int64)
    steps = np.zeros(B, dtype=np.int64)
    regs = np.zeros((B, isa.NUM_REGS), dtype=np.int64)
    fault = np.tile(NO_FAULT, (B, 1))
    pos = 0
    for d in range(n_dev):
        c = int(plan.device_counts[d])
        lanes = plan.order[pos:pos + c]
        ret[lanes] = out.ret[d, :c]
        status[lanes] = out.status[d, :c]
        steps[lanes] = out.steps[d, :c]
        regs[lanes] = out.regs[d, :c]
        fault[lanes] = out.fault[d, :c]
        pos += c
    return BatchedInvokeResult(mem=out.mem, ret=ret, status=status,
                               steps=steps, regs=regs, fault=fault)


@dataclasses.dataclass
class InvokeResult:
    mem: np.ndarray
    ret: int
    status: int
    steps: int
    regs: np.ndarray
    fault: Optional[isa.FaultInfo] = None

    @property
    def ok(self) -> bool:
        return self.status == isa.STATUS_OK


@dataclasses.dataclass
class BatchedInvokeResult:
    mem: np.ndarray
    ret: np.ndarray       # i64 [B]
    status: np.ndarray    # i64 [B]
    steps: np.ndarray     # i64 [B]
    regs: np.ndarray      # i64 [B, 16]
    fault: Optional[np.ndarray] = None   # i64 [B, 4] FaultInfo rows

    @property
    def ok(self) -> np.ndarray:
        return self.status == isa.STATUS_OK

    def fault_at(self, b: int) -> Optional[isa.FaultInfo]:
        """The decoded FaultInfo of lane ``b`` (None when clean)."""
        if self.fault is None:
            return None
        return fault_info(np.asarray(self.fault)[b])
