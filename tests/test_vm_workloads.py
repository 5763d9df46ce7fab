"""The six paper workloads executed on both VMs (JAX == pyvm oracle)."""

import numpy as np
import pytest

from repro.core import isa, memory, pyvm, vm
from repro.core.memory import Grant
from repro.core.registry import OperatorRegistry
from repro.core.verifier import verify
from repro.core import operators as ops


def run_both(vop, rt, mem, params, home=0, failed=None):
    r1 = pyvm.run(vop, rt, mem.copy(), params, home=home,
                  failed=failed or set())
    r2 = vm.invoke(vop, rt, mem.copy(), params, home=home, failed=failed)
    assert (r1.ret, r1.status, r1.steps) == (r2.ret, r2.status, r2.steps)
    assert np.array_equal(r1.mem, r2.mem)
    return r2


def _graph_populate_loop(n_nodes, seed):
    """The per-node loop ``GraphWalk.populate`` replaced: the reference
    its vectorized form must reproduce word for word."""
    rng = np.random.default_rng(seed)
    order = rng.permutation(n_nodes)
    graph = np.zeros(n_nodes * ops.NODE_WORDS, dtype=np.int64)
    for i in range(n_nodes):
        node, nxt = order[i], order[(i + 1) % n_nodes]
        base = node * ops.NODE_WORDS
        graph[base] = 10_000 + node
        graph[base + 1] = nxt * ops.NODE_WORDS
        graph[base + 2: base + ops.NODE_WORDS] = \
            rng.integers(0, 1 << 32, size=ops.NODE_WORDS - 2)
    return order, graph


@pytest.mark.parametrize("n_nodes,seed", [(1, 0), (2, 5), (64, 0),
                                          (1000, 3)])
def test_graph_populate_matches_loop(n_nodes, seed):
    w = ops.GraphWalk(n_nodes=n_nodes)
    rt = w.regions()
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt, seed=seed)
    ref_order, ref_graph = _graph_populate_loop(n_nodes, seed)
    assert np.array_equal(order, ref_order)
    got = memory.read_region(mem, rt, 0, "graph", count=ref_graph.size)
    assert np.array_equal(got, ref_graph)


def test_graph_walk_depths():
    w = ops.GraphWalk(n_nodes=128, max_depth=32)
    rt = w.regions()
    vop = verify(w.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    for depth in (0, 1, 7, 31):
        start = int(order[5])
        r = run_both(vop, rt, mem, [start * 8, depth])
        assert r.ok and r.ret == w.reference(order, start, depth)


def test_ptw3_translations():
    p = ops.PageTableWalk(fanout=16, n_pages=32)
    rt = p.regions()
    vop = verify(p.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    vamap = p.populate(mem, rt)
    for va, ppage in list(vamap.items())[:4]:
        r = run_both(vop, rt, mem, [va])
        assert r.ok and r.ret == ppage
        reply = memory.read_region(r.mem, rt, 0, "reply")
        data = memory.read_region(mem, rt, 0, "data", ppage,
                                  ops.PAGE_WORDS)
        assert np.array_equal(reply, data)


def test_dist_lock_paths():
    d = ops.DistLock()
    rt = d.regions()
    vop = verify(d.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(3, rt)
    memory.write_region(mem, rt, 0, "lock", [0, 42])
    params = [0, 1, 777, 1, 1, 2, 1]
    r = run_both(vop, rt, mem, params)
    assert r.ok and r.ret == 42
    assert r.mem[1, rt["lock"].base + 1] == 777
    assert r.mem[2, rt["lock"].base + 1] == 777
    assert r.mem[0, rt["lock"].base] == 0          # released

    held = mem.copy()
    held[0, rt["lock"].base] = 1
    r = run_both(vop, rt, held, params)
    assert r.status == isa.STATUS_FAIL             # bounded retry then FAIL

    r = run_both(vop, rt, mem, params, failed={2})
    assert r.ok and r.regs[isa.ERR_REG] == 1       # error flag, no fault
    assert r.mem[2, rt["lock"].base + 1] != 777    # failed replica skipped


@pytest.mark.parametrize("block_bytes", [4096, 65536])
def test_paged_kv_fetch(block_bytes):
    k = ops.PagedKVFetch(n_blocks_pool=16, block_bytes=block_bytes,
                         max_req_blocks=4)
    rt = k.regions()
    vop = verify(k.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    table = k.populate(mem, rt)
    ids = [3, 9, 1]
    k.make_request(mem, rt, ids)
    r = run_both(vop, rt, mem, [len(ids)])
    exp = k.reference(mem, rt, table, ids)
    got = memory.read_region(r.mem, rt, 0, "reply", 0, exp.size)
    assert np.array_equal(got, exp)


def test_paged_kv_fetch_remote_reply():
    k = ops.PagedKVFetch(n_blocks_pool=16, block_bytes=4096,
                         max_req_blocks=4)
    rt = k.regions()
    vop = verify(k.build(rt, remote_reply=True), grant=Grant.all_of(rt),
                 regions=rt)
    mem = memory.make_pool(2, rt)
    table = k.populate(mem, rt)
    ids = [5, 2]
    k.make_request(mem, rt, ids)
    r = run_both(vop, rt, mem, [2, 1])     # client = device 1
    exp = k.reference(mem, rt, table, ids)
    got = memory.read_region(r.mem, rt, 1, "reply", 0, exp.size)
    assert np.array_equal(got, exp)
    untouched = memory.read_region(r.mem, rt, 0, "reply", 0, exp.size)
    assert not np.array_equal(untouched, exp)


def test_moe_gather():
    m = ops.MoEExpertGather(n_experts=32, max_k=8)
    rt = m.regions()
    vop = verify(m.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    table = m.populate(mem, rt)
    eids = [7, 0, 31, 12]
    memory.write_region(mem, rt, 0, "expert_ids",
                        np.asarray(eids, dtype=np.int64))
    r = run_both(vop, rt, mem, [len(eids)])
    w0 = memory.read_region(mem, rt, 0, "weights")
    exp = np.concatenate([w0[int(table[e]):int(table[e])
                             + ops.MOE_SLAB_WORDS] for e in eids])
    got = memory.read_region(r.mem, rt, 0, "reply", 0, exp.size)
    assert np.array_equal(got, exp)


def test_nsa_select():
    s = ops.NSASelect(n_scores=16, block_words=64)
    rt = s.regions()
    vop = verify(s.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    scores, blockmap = s.populate(mem, rt)
    thr = 40
    r = run_both(vop, rt, mem, [16, thr])
    sel = [i for i in range(16) if scores[i] >= thr]
    assert r.ret == len(sel)


def test_registry_multi_tenant_isolation():
    w = ops.GraphWalk(n_nodes=64)
    rt = w.regions()
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "alice"))
    reg.add_tenant(Grant.of("bob", readable=[rt.rid("reply")]))
    op_id = reg.register("alice", w.build(rt))
    with pytest.raises(Exception):
        reg.register("bob", w.build(rt))
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    r = reg._invoke(op_id, mem, [int(order[0]) * 8, 3])
    assert r.ret == w.reference(order, int(order[0]), 3)
    assert reg.dispatch_table()[op_id] == 0
    assert len(reg) == 1


def test_fuel_bound_is_never_hit():
    """The verified step bound is the VM fuel; a terminating operator must
    finish strictly under it."""
    w = ops.GraphWalk(n_nodes=64, max_depth=16)
    rt = w.regions()
    vop = verify(w.build(rt), grant=Grant.all_of(rt), regions=rt)
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    r = vm.invoke(vop, rt, mem, [int(order[0]) * 8, 16])
    assert r.status != isa.STATUS_FUEL
    assert r.steps <= vop.step_bound
