"""Batch-parallel engine and trace-compiled fast path vs the pyvm oracle.

Parity contract (see ``core/vm.py`` docstring): batched execution is the
deterministic round-robin interleaving of its requests.  When request
footprints are disjoint that is bit-identical to running them one after
another on ``pyvm``; under contention the ordering stays deterministic
(lowest request index wins a contended atomic).
"""

import numpy as np
import pytest

from repro.core import compile as tc
from repro.core import isa, memory, pyvm, vm
from repro.core.isa import Alu
from repro.core.memory import Grant, merge_tables
from repro.core import operators as ops
from repro.core.program import OperatorBuilder
from repro.core.registry import OperatorRegistry
from repro.core.verifier import verify


def sequential_oracle(vop, rt, mem, params, homes=None, failed=None):
    """Run the batch one request at a time on pyvm (shared memory)."""
    seq = mem.copy()
    rets, stats, steps = [], [], []
    for i, p in enumerate(params):
        home = homes[i] if homes is not None else 0
        r = pyvm.run(vop, rt, seq, p, home=home, failed=failed or set())
        rets.append(r.ret)
        stats.append(r.status)
        steps.append(r.steps)
    return seq, np.array(rets), np.array(stats), np.array(steps)


def assert_batch_matches(res, seq_mem, rets, stats, steps):
    assert np.array_equal(res.ret, rets), (res.ret, rets)
    assert np.array_equal(res.status, stats)
    assert np.array_equal(res.steps, steps)
    assert np.array_equal(res.mem, seq_mem)


# ---------------------------------------------------------------------------
# Batched interpreter vs sequential pyvm (disjoint requests)
# ---------------------------------------------------------------------------

def test_batched_graph_walk_parity():
    w = ops.GraphWalk(n_nodes=128, max_depth=16, reply_words=16 * 8)
    rt = w.regions()
    vop = verify(w.build(rt, reply_param=True), grant=Grant.all_of(rt),
                 regions=rt)
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    B = 12
    params = [[int(order[i]) * 8, (3 * i) % 16, i * ops.NODE_WORDS]
              for i in range(B)]
    res = vm.invoke_batched(vop, rt, mem, params)
    assert_batch_matches(res, *sequential_oracle(vop, rt, mem, params))
    for i in range(B):
        assert res.ret[i] == w.reference(order, int(order[i]),
                                         (3 * i) % 16)


def test_batched_ptw_parity():
    p = ops.PageTableWalk(fanout=16, n_pages=32, reply_pages=8)
    rt = p.regions()
    vop = verify(p.build(rt, reply_param=True), grant=Grant.all_of(rt),
                 regions=rt)
    mem = memory.make_pool(1, rt)
    vamap = p.populate(mem, rt)
    items = list(vamap.items())[:8]
    params = [[va, i * ops.PAGE_WORDS] for i, (va, _) in enumerate(items)]
    res = vm.invoke_batched(vop, rt, mem, params)
    assert_batch_matches(res, *sequential_oracle(vop, rt, mem, params))
    for i, (_, ppage) in enumerate(items):
        assert res.ret[i] == ppage


def test_batched_per_request_homes():
    """Requests executing from different hosts write their own pools."""
    w = ops.GraphWalk(n_nodes=64, max_depth=8)
    rt = w.regions()
    vop = verify(w.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(3, rt)
    orders = [w.populate(mem, rt, device=d, seed=d) for d in range(3)]
    homes = [0, 1, 2]
    params = [[int(orders[d][0]) * 8, 5] for d in range(3)]
    res = vm.invoke_batched(vop, rt, mem, params, homes=homes)
    assert_batch_matches(res, *sequential_oracle(vop, rt, mem, params,
                                                 homes=homes))
    for d in range(3):
        assert res.ret[d] == w.reference(orders[d], int(orders[d][0]), 5)


@pytest.mark.parametrize("wl,params", [
    ("kv", None), ("moe", None), ("nsa", None)])
def test_batched_identical_requests_all_ops(wl, params):
    """Every seed operator: B identical requests == one pyvm run (their
    effects are idempotent), exercising the conflict-serialized path."""
    if wl == "kv":
        k = ops.PagedKVFetch(n_blocks_pool=16, block_bytes=4096,
                             max_req_blocks=4)
        rt = k.regions()
        vop = verify(k.build(rt), grant=Grant.all_of(rt), regions=rt)
        mem = memory.make_pool(1, rt)
        k.populate(mem, rt)
        k.make_request(mem, rt, [3, 9, 1])
        p = [3]
    elif wl == "moe":
        m = ops.MoEExpertGather(n_experts=32, max_k=8)
        rt = m.regions()
        vop = verify(m.build(rt), grant=Grant.all_of(rt), regions=rt)
        mem = memory.make_pool(1, rt)
        m.populate(mem, rt)
        memory.write_region(mem, rt, 0, "expert_ids",
                            np.asarray([7, 0, 31, 12], dtype=np.int64))
        p = [4]
    else:
        s = ops.NSASelect(n_scores=16, block_words=64)
        rt = s.regions()
        vop = verify(s.build(rt), grant=Grant.all_of(rt), regions=rt)
        mem = memory.make_pool(1, rt)
        s.populate(mem, rt)
        p = [16, 40]
    B = 5
    res = vm.invoke_batched(vop, rt, mem, [list(p)] * B)
    one = pyvm.run(vop, rt, mem.copy(), p)
    assert np.all(res.ret == one.ret)
    assert np.all(res.status == one.status)
    assert np.all(res.steps == one.steps)
    assert np.array_equal(res.mem, one.mem)


# ---------------------------------------------------------------------------
# Contention: deterministic winner ordering
# ---------------------------------------------------------------------------

def _cas_race_op(rt):
    """Each request CASes latch 0 -> its token and returns the old value."""
    b = OperatorBuilder("cas_race", n_params=1, regions=rt)
    zero = b.const(0)
    old = b.reg()
    b.cas(old, "lock", zero, cmp=zero, swap=b.param(0))
    b.ret(old)
    return b.build()


def test_contended_cas_deterministic_winner():
    rt = memory.packed_table([("lock", 64)])
    vop = verify(_cas_race_op(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    B = 8
    params = [[100 + i] for i in range(B)]
    res = vm.invoke_batched(vop, rt, mem, params)
    # all B requests hit the CAS in the same macro-step: round-robin order
    # serializes them, so request 0 wins and everyone else observes its
    # token — deterministically
    assert res.ret[0] == 0
    assert np.all(res.ret[1:] == 100)
    assert res.mem[0, rt["lock"].base] == 100
    res2 = vm.invoke_batched(vop, rt, mem, params)
    assert np.array_equal(res.mem, res2.mem)
    assert np.array_equal(res.ret, res2.ret)


def test_contended_dist_lock_deterministic():
    d = ops.DistLock(max_retries=8)
    rt = d.regions()
    vop = verify(d.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(3, rt)
    memory.write_region(mem, rt, 0, "lock", [0, 42])
    B = 4
    params = [[0, 1, 1000 + i, 1, 1, 2, 1] for i in range(B)]
    res = vm.invoke_batched(vop, rt, mem, params)
    res2 = vm.invoke_batched(vop, rt, mem, params)
    assert np.array_equal(res.ret, res2.ret)
    assert np.array_equal(res.mem, res2.mem)
    winners = [i for i in range(B) if res.status[i] == isa.STATUS_OK]
    assert winners, "someone must acquire the lock"
    assert winners[0] == 0, "request 0 reaches the CAS first and must win"
    assert res.ret[0] == 42                      # saw the initial state
    # the lock state holds the last winner's value, replicated to 1 and 2
    final = res.mem[0, rt["lock"].base + 1]
    assert final == 1000 + winners[-1]
    assert res.mem[1, rt["lock"].base + 1] == final
    assert res.mem[2, rt["lock"].base + 1] == final
    # latch released by the last holder
    assert res.mem[0, rt["lock"].base] == 0


# ---------------------------------------------------------------------------
# Trace-compiled fast path vs interpreter — every seed operator
# ---------------------------------------------------------------------------

def _compiled_check(name, vop, rt, mem, params, home=0, failed=None):
    r1 = pyvm.run(vop, rt, mem.copy(), params, home=home,
                  failed=failed or set())
    rc = tc.invoke_compiled(vop, rt, mem.copy(), [list(params)], homes=home,
                            failed=failed)
    assert rc.ret[0] == r1.ret, name
    assert rc.status[0] == r1.status, name
    assert rc.steps[0] == r1.steps, name
    assert np.array_equal(rc.regs[0], np.array(r1.regs)), name
    assert np.array_equal(rc.mem, r1.mem), name


def test_compiled_equals_pyvm_graph_walk():
    w = ops.GraphWalk(n_nodes=128, max_depth=32)
    rt = w.regions()
    vop = verify(w.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    for depth in (0, 1, 7, 31):
        _compiled_check("graph", vop, rt, mem, [int(order[5]) * 8, depth])


def test_compiled_protected_graph_walk_at_benchmark_depth():
    """The benchmark's GraphWalk cap (max_depth=64) under runtime
    protection.  Each hop's address and halt mask feed each other, so a
    fully unrolled walk ran in time exponential in the cap on XLA:CPU;
    a loop trip per iteration keeps it linear."""
    w = ops.GraphWalk(n_nodes=128, max_depth=64)
    rt = w.regions()
    vop = verify(w.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    start = int(order[5]) * 8
    for depth in (0, 10, 63):
        _compiled_check("graph64", vop, rt, mem, [start, depth])
    # a torn pointer read at hop 6 faults hop 7, mid-loop: the fault row
    # latched inside the loop matches the oracle's
    torn = mem.copy()
    torn[0, rt["graph"].base + int(order[10]) * 8 + 1] = -77
    _compiled_check("graph64 torn", vop, rt, torn, [start, 63])
    r1 = pyvm.run(vop, rt, torn.copy(), [start, 63])
    rc = tc.invoke_compiled(vop, rt, torn.copy(), [[start, 63]])
    assert r1.status == isa.STATUS_PROT_FAULT and r1.fault.addr == -76
    assert rc.fault_at(0) == r1.fault


def test_compiled_loop_lanes_leave_at_different_trips():
    """The lanes of one wave leave a loop at different trips: by their
    trip count (0, 5), by a break, by a RET in the body, by a fault
    (a load past the region) or at the cap.  The compiled loop runs
    until its last live lane is done and matches pyvm lane by lane."""
    rt = memory.packed_table([("data", 64)])
    b = OperatorBuilder("exits", n_params=3, regions=rt)
    acc = b.const(0)
    k = b.const(0)
    v = b.reg()
    out = b.mklabel("out")
    stay = b.mklabel("stay")
    with b.loop((b.param(0), 100)):
        b.jump(out, k, Alu.EQ, b.param(1))          # break
        b.jump(stay, k, Alu.NE, b.param(2))
        b.ret(acc)                                  # RET inside the body
        b.bind(stay)
        b.load(v, "data", k)                        # faults at k = 64
        b.add(acc, acc, v)
        b.add(k, k, 1)
    b.bind(out)
    b.ret(acc)
    vop = verify(b.build(), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    mem[0, :64] = np.arange(64) * 3 + 1
    params = [[0, -1, -1], [5, -1, -1], [100, 10, -1], [100, -1, 20],
              [100, -1, -1], [50, -1, -1]]
    rc = tc.invoke_compiled(vop, rt, mem.copy(), params)
    stats = []
    for lane, p in enumerate(params):
        r1 = pyvm.run(vop, rt, mem.copy(), p)
        stats.append(r1.status)
        assert rc.ret[lane] == r1.ret, lane
        assert rc.status[lane] == r1.status, lane
        assert rc.steps[lane] == r1.steps, lane
        assert np.array_equal(rc.regs[lane], np.array(r1.regs)), lane
        assert rc.fault_at(lane) == r1.fault, lane
    assert stats.count(isa.STATUS_PROT_FAULT) == 1
    assert np.array_equal(rc.mem, mem)



def test_compiled_equals_pyvm_ptw3():
    p = ops.PageTableWalk(fanout=16, n_pages=32)
    rt = p.regions()
    vop = verify(p.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    vamap = p.populate(mem, rt)
    for va, _ in list(vamap.items())[:3]:
        _compiled_check("ptw3", vop, rt, mem, [va])


def test_compiled_equals_pyvm_dist_lock():
    d = ops.DistLock()
    rt = d.regions()
    vop = verify(d.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(3, rt)
    memory.write_region(mem, rt, 0, "lock", [0, 42])
    params = [0, 1, 777, 1, 1, 2, 1]
    _compiled_check("lock free", vop, rt, mem, params)
    held = mem.copy()
    held[0, rt["lock"].base] = 1
    _compiled_check("lock held", vop, rt, held, params)
    _compiled_check("lock failed-replica", vop, rt, mem, params, failed={2})


@pytest.mark.parametrize("block_bytes", [4096, 65536])
def test_compiled_equals_pyvm_kv_fetch(block_bytes):
    k = ops.PagedKVFetch(n_blocks_pool=16, block_bytes=block_bytes,
                         max_req_blocks=4)
    rt = k.regions()
    vop = verify(k.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    k.populate(mem, rt)
    k.make_request(mem, rt, [3, 9, 1])
    _compiled_check("kv", vop, rt, mem, [3])


def test_compiled_equals_pyvm_moe_and_superop():
    m = ops.MoEExpertGather(n_experts=32, max_k=8)
    rt = m.regions()
    vop = verify(m.build(rt), grant=Grant.all_of(rt), regions=rt)
    assert len(tc.find_gather_chains(vop)) == 1    # the fused superop
    mem = memory.make_pool(1, rt)
    m.populate(mem, rt)
    memory.write_region(mem, rt, 0, "expert_ids",
                        np.asarray([7, 0, 31, 12], dtype=np.int64))
    _compiled_check("moe", vop, rt, mem, [4])
    # with the fused superoperator disabled the generic unroll must agree
    r1 = pyvm.run(vop, rt, mem.copy(), [4])
    rg = tc.invoke_compiled(vop, rt, mem.copy(), [[4]], superops=False)
    assert rg.ret[0] == r1.ret and np.array_equal(rg.mem, r1.mem)


def test_compiled_equals_pyvm_nsa():
    s = ops.NSASelect(n_scores=16, block_words=64)
    rt = s.regions()
    vop = verify(s.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    s.populate(mem, rt)
    for thr in (0, 40, 101):
        _compiled_check("nsa", vop, rt, mem, [16, thr])


def test_compiled_batched_matches_batched_interpreter():
    w = ops.GraphWalk(n_nodes=128, max_depth=16, reply_words=16 * 8)
    rt = w.regions()
    vop = verify(w.build(rt, reply_param=True), grant=Grant.all_of(rt),
                 regions=rt)
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    B = 16
    params = [[int(order[i]) * 8, i % 16, i * ops.NODE_WORDS]
              for i in range(B)]
    ri = vm.invoke_batched(vop, rt, mem, params)
    rc = tc.invoke_compiled(vop, rt, mem.copy(), params)
    assert np.array_equal(ri.ret, rc.ret)
    assert np.array_equal(ri.status, rc.status)
    assert np.array_equal(ri.steps, rc.steps)
    assert np.array_equal(ri.mem, rc.mem)


def test_compiled_double_buffer_bit_parity():
    """The double-buffered gather-chain schedule (chunked: chunk k+1's
    gather issued before chunk k's scatter) must stay bit-identical to
    the monolithic compiled path and to the sequential pyvm oracle —
    the chain cap (32) exceeds DBUF_CHUNK so the chunked path really
    runs."""
    m = ops.MoEExpertGather(n_experts=64, max_k=32, slab_words=64,
                            reply_slots=8)
    rt = m.regions()
    vop = verify(m.build(rt, reply_param=True), grant=Grant.all_of(rt),
                 regions=rt)
    assert len(tc.find_gather_chains(vop)) == 1
    assert tc.find_gather_chains(vop)[0].cap > tc.DBUF_CHUNK
    mem = memory.make_pool(1, rt)
    m.populate(mem, rt)
    memory.write_region(mem, rt, 0, "expert_ids",
                        np.arange(32, dtype=np.int64) % 64)
    B = 6
    params = [[5 + (i % 7), i * 32 * 64] for i in range(B)]
    seq, rets, stats, steps = sequential_oracle(vop, rt, mem, params)
    plain = tc.invoke_compiled(vop, rt, mem.copy(), params)
    dbuf = tc.invoke_compiled(vop, rt, mem.copy(), params,
                              double_buffer=True)
    assert_batch_matches(plain, seq, rets, stats, steps)
    assert_batch_matches(dbuf, seq, rets, stats, steps)
    # forced through the registry mode (the endpoint's "compiled_dbuf")
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    op_id = reg.register("t", m.build(rt, reply_param=True))
    assert reg[op_id].chain_iters == 32
    rr = reg._invoke_batched(op_id, mem.copy(), params,
                             mode="compiled_dbuf")
    assert_batch_matches(rr, seq, rets, stats, steps)
    # a chain that fits one chunk is not double-bufferable: it must
    # not count toward the dbuf candidate (the engine would emit the
    # monolithic schedule, so there is no overlap win to price)
    short = ops.MoEExpertGather(n_experts=64, max_k=4, slab_words=64)
    rt2 = short.regions()
    reg2 = OperatorRegistry(rt2)
    reg2.add_tenant(Grant.all_of(rt2, "t"))
    sid = reg2.register("t", short.build(rt2))
    assert reg2[sid].n_gather_chains == 1
    assert reg2[sid].chain_iters == 0


def test_compiled_gather_kernel_route_matches():
    """The tiara_gather Pallas route (interpret mode) == the XLA lowering."""
    m = ops.MoEExpertGather(n_experts=32, max_k=8)
    rt = m.regions()
    vop = verify(m.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    m.populate(mem, rt)
    memory.write_region(mem, rt, 0, "expert_ids",
                        np.asarray([5, 2, 9], dtype=np.int64))
    rx = tc.invoke_compiled(vop, rt, mem.copy(), [[3]], impl="xla")
    rk = tc.invoke_compiled(vop, rt, mem.copy(), [[3]],
                            impl="kernel_interpret")
    assert np.array_equal(rx.mem, rk.mem)
    assert np.array_equal(rx.ret, rk.ret)


# ---------------------------------------------------------------------------
# Registry dispatch
# ---------------------------------------------------------------------------

def test_registry_slot_entry_points():
    w = ops.GraphWalk(n_nodes=64, max_depth=8, reply_words=8 * 8)
    rt = w.regions()
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "alice"))
    op_id = reg.register("alice", w.build(rt, reply_param=True))
    slot = reg[op_id]
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    params = [[int(order[i]) * 8, 3, i * ops.NODE_WORDS] for i in range(4)]
    r_int = reg._invoke_batched(op_id, mem, params, mode="batched")
    r_cmp = reg._invoke_batched(op_id, mem, params, mode="compiled")
    r_auto = reg._invoke_batched(op_id, mem, params, mode="auto")
    for r in (r_cmp, r_auto):
        assert np.array_equal(r_int.ret, r.ret)
        assert np.array_equal(r_int.mem, r.mem)
    # single-request modes agree too
    r1 = reg._invoke(op_id, mem, params[0], mode="interp")
    r2 = reg._invoke(op_id, mem, params[0], mode="compiled")
    assert (r1.ret, r1.status, r1.steps) == (r2.ret, r2.status, r2.steps)
    assert np.array_equal(r1.mem, r2.mem)
    assert "compiled" in reg.dump()


# ---------------------------------------------------------------------------
# Mixed-op batches: many tenants' operators in one lockstep launch
# ---------------------------------------------------------------------------

def _mixed_stock_setup(B=128, seed=7):
    """Six stock operators from six tenants in one shared pool, with a
    random interleaving whose footprints make lockstep round-robin
    bit-identical to sequential per-request pyvm: GraphWalk/PTW/KV/MoE
    requests write disjoint reply slots, DistLock requests take disjoint
    latches, and NSA requests within the tenant are identical (idempotent
    reply writes — these exercise the serialized contended path inside
    the mixed wave)."""
    gw = ops.GraphWalk(n_nodes=64, max_depth=8, reply_words=32 * 8)
    ptw = ops.PageTableWalk(fanout=16, n_pages=8, reply_pages=32)
    lk = ops.DistLock(max_retries=2)
    kv = ops.PagedKVFetch(n_blocks_pool=16, block_bytes=1024,
                          max_req_blocks=4, reply_slots=32)
    moe = ops.MoEExpertGather(n_experts=16, max_k=4, slab_words=64,
                              reply_slots=32)
    nsa = ops.NSASelect(n_scores=16, block_words=32)
    combined, views = merge_tables([
        ("gw", gw.regions()), ("ptw", ptw.regions()),
        ("lk", lk.regions()), ("kv", kv.regions()),
        ("moe", moe.regions()), ("nsa", nsa.regions())])
    reg = OperatorRegistry(combined, n_devices=3)
    for t, v in views.items():
        reg.add_tenant(Grant.all_of(v, t))
    reg.register("gw", gw.build(views["gw"], reply_param=True))
    reg.register("ptw", ptw.build(views["ptw"], reply_param=True))
    reg.register("lk", lk.build(views["lk"]))
    reg.register("kv", kv.build(views["kv"], reply_param=True))
    reg.register("moe", moe.build(views["moe"], reply_param=True))
    reg.register("nsa", nsa.build(views["nsa"]))

    mem = memory.make_pool(3, combined)
    order = gw.populate(mem, views["gw"])
    vamap = ptw.populate(mem, views["ptw"])
    kv.populate(mem, views["kv"])
    kv.make_request(mem, views["kv"], [3, 9, 1])
    moe.populate(mem, views["moe"])
    memory.write_region(mem, views["moe"], 0, "expert_ids",
                        np.asarray([5, 2, 9], dtype=np.int64))
    nsa.populate(mem, views["nsa"])
    vas = sorted(vamap.keys())

    rng = np.random.default_rng(seed)
    ids = np.concatenate([np.arange(6)] * (B // 6 + 1))[:B]
    rng.shuffle(ids)
    slot = [0] * 6
    params = []
    for op_id in ids:
        j = slot[op_id]
        slot[op_id] += 1
        if op_id == 0:
            params.append([int(order[j % 64]) * 8, (3 * j) % 8,
                           j % 32 * ops.NODE_WORDS])
        elif op_id == 1:
            params.append([int(vas[j % len(vas)]),
                           j % 32 * ops.PAGE_WORDS])
        elif op_id == 2:                      # disjoint latch/state pairs
            params.append([2 * (j % 32), 2 * (j % 32) + 1, 1000 + j,
                           1, 2 * (j % 32) + 1, 2, 2 * (j % 32) + 1])
        elif op_id == 3:                      # varied n, disjoint slots
            params.append([1 + j % 3, (j % 32) * 4 * 128])
        elif op_id == 4:                      # varied k, disjoint slots
            params.append([1 + j % 4, (j % 32) * 4 * 64])
        else:
            params.append([16, 40])
    return reg, mem, list(ids), params


def test_mixed_batch_parity_all_stock_ops():
    """B=128 random interleaving of every stock operator: every mixed
    dispatch mode is bit-identical to the per-request pyvm oracle."""
    reg, mem, ids, params = _mixed_stock_setup(B=128)
    vops = reg.store_ops()
    seq = mem.copy()
    rets, stats, steps = [], [], []
    for op_id, p in zip(ids, params):
        r = pyvm.run(vops[op_id], reg.regions, seq, p)
        rets.append(r.ret)
        stats.append(r.status)
        steps.append(r.steps)
    for mode in ("mixed", "segmented", "serial", "auto"):
        res = reg._invoke_mixed(ids, mem, params, mode=mode)
        assert_batch_matches(res, seq, np.array(rets), np.array(stats),
                             np.array(steps))


def test_mixed_engine_level_parity():
    """vm.invoke_batched_mixed (below the registry) agrees with pyvm."""
    reg, mem, ids, params = _mixed_stock_setup(B=36, seed=3)
    vops = reg.store_ops()
    res = vm.invoke_batched_mixed(vops, reg.regions, mem, ids, params)
    seq = mem.copy()
    for op_id, p in zip(ids, params):
        pyvm.run(vops[op_id], reg.regions, seq, p)
    assert np.array_equal(res.mem, seq)


def test_mixed_contended_store_cas_deterministic():
    """A mixed STORE/CAS race on one shared latch: round-robin order
    serializes the contended macro-step, so the lowest-indexed CAS lane
    wins deterministically and later STORE lanes overwrite in index
    order."""
    rt = memory.packed_table([("lock", 64)])
    cas_op = _cas_race_op(rt)                 # movi; cas(0 -> 100+i); ret
    sb = OperatorBuilder("store_then_load", n_params=1, regions=rt)
    off = sb.const(0)
    sb.store(sb.param(0), "lock", off)
    got = sb.load(sb.reg(), "lock", off)
    sb.ret(got)
    store_op = sb.build()
    v_cas = verify(cas_op, grant=Grant.all_of(rt), regions=rt)
    v_store = verify(store_op, grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    ids = [0, 1, 0, 1]                        # CAS, STORE, CAS, STORE
    params = [[100], [201], [102], [203]]
    res = vm.invoke_batched_mixed([v_cas, v_store], rt, mem, ids, params)
    # macro-step with the contended word, serialized in request order:
    #   req0 CAS sees 0 (wins, latch=100); req1 stores 201; req2 CAS
    #   sees 201 (loses); req3 stores 203.  The STORE ops' trailing
    #   loads then both observe 203.
    assert list(res.ret) == [0, 203, 201, 203]
    assert res.mem[0, rt["lock"].base] == 203
    res2 = vm.invoke_batched_mixed([v_cas, v_store], rt, mem, ids, params)
    assert np.array_equal(res.ret, res2.ret)
    assert np.array_equal(res.mem, res2.mem)


def test_registry_compiles_long_loop():
    """A loop far past any unroll budget (8000 trips) is traced once and
    runs on the compiled path, equal to the batched interpreter."""
    rt = memory.packed_table([("data", 1024)])
    b = OperatorBuilder("big_loop", n_params=1, regions=rt)
    i = b.mov(b.reg(), b.param(0))
    v = b.reg()
    j = b.reg()
    with b.loop(8000):
        b.band(j, i, 1023)                # stay inside the 1024-word grant
        b.load(v, "data", j)
        b.add(i, i, 1)
    b.ret(v)
    reg = OperatorRegistry(rt, max_steps=1 << 20)
    reg.add_tenant(Grant.all_of(rt, "t"))
    op_id = reg.register("t", b.build())
    mem = memory.make_pool(1, rt)
    mem[0, :1024] = np.arange(1024)
    params = [[0], [5]]
    r_int = reg._invoke_batched(op_id, mem, params, mode="batched")
    r_cmp = reg._invoke_batched(op_id, mem, params, mode="compiled")
    assert list(r_cmp.ret) == [7999 & 1023, 8004 & 1023]
    assert np.all(r_cmp.status == isa.STATUS_OK)
    for f in ("ret", "status", "steps", "mem"):
        assert np.array_equal(getattr(r_cmp, f), getattr(r_int, f)), f
