"""Mixed-batch planner, dispatch cost model, and registry control plane.

The mixed data path itself (lockstep engine vs pyvm oracle) is covered in
``test_batched_vm.py``; this file covers the pieces around it: the
stable-sort segmentation plan, the analytical cost model's decisions, and
the registry's validation / capacity / dispatch bookkeeping.
"""

import numpy as np
import pytest

from repro.core import compile as tc
from repro.core import isa, memory
from repro.core.costmodel import (DispatchCostModel, EngineCost,
                                  SegmentStats, op_mix_entropy)
from repro.core.memory import Grant, merge_tables
from repro.core import operators as ops
from repro.core.program import OperatorBuilder
from repro.core.registry import OperatorRegistry, RegistrationError


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def test_plan_mixed_batch_stable_segments():
    ids = [2, 0, 1, 0, 2, 0]
    plan = tc.plan_mixed_batch(ids)
    assert [s.op_id for s in plan.segments] == [0, 1, 2]
    assert [s.size for s in plan.segments] == [3, 1, 2]
    # stable: arrival order preserved within each segment
    assert list(plan.segment_indices(plan.segments[0])) == [1, 3, 5]
    assert list(plan.segment_indices(plan.segments[2])) == [0, 4]
    # inverse really is the inverse permutation
    assert np.array_equal(plan.order[plan.inverse], np.arange(6))
    sorted_ids = plan.op_ids[plan.order]
    assert list(sorted_ids) == sorted(ids)


def test_plan_mixed_batch_single_op_and_errors():
    plan = tc.plan_mixed_batch([5, 5, 5])
    assert plan.n_segments == 1 and plan.segments[0].size == 3
    with pytest.raises(ValueError):
        tc.plan_mixed_batch([])
    with pytest.raises(ValueError):
        tc.plan_mixed_batch([[1, 2]])


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

def test_op_mix_entropy():
    assert op_mix_entropy([3, 3, 3, 3]) == 0.0
    assert op_mix_entropy([0, 1, 2, 3]) == pytest.approx(2.0)
    assert 0.0 < op_mix_entropy([0, 0, 0, 1]) < 1.0


def test_choose_batched_prefers_compiled_when_clean():
    cm = DispatchCostModel()
    d = cm.choose_batched(batch=256, step_bound=40)
    assert d.mode == "compiled"
    assert d.costs["compiled"] < d.costs["batched"]


def test_choose_batched_contention_forces_interpreter():
    """The compiled trace cannot serialize contended non-atomic writes,
    so any contention hint must keep the wave on the exact interpreter."""
    cm = DispatchCostModel()
    d = cm.choose_batched(batch=256, step_bound=40, contention_rate=0.5)
    assert d.mode == "batched"
    assert "compiled" not in d.costs


def test_choose_mixed_few_big_segments_vs_many_small():
    cm = DispatchCostModel()
    # 4 big segments: per-segment compiled launches win
    big = [SegmentStats(size=256, step_bound=40)] * 4
    d = cm.choose_mixed(segments=big)
    assert d.mode == "segmented"
    assert d.entropy_bits == pytest.approx(2.0)
    # 64 tiny segments: per-segment launch overhead dominates, the
    # one-launch mixed engine wins
    tiny = [SegmentStats(size=2, step_bound=40)] * 64
    d2 = cm.choose_mixed(segments=tiny)
    assert d2.mode == "mixed"
    assert d2.entropy_bits == pytest.approx(6.0)
    assert d2.costs["mixed"] < d2.costs["segmented"]


def test_choose_mixed_contention_pins_round_robin():
    """Segmentation reorders requests across ops, which breaks the
    reference round-robin interleaving for contended footprints — so a
    contended wave must stay on the one-launch mixed engine."""
    cm = DispatchCostModel()
    segs = [SegmentStats(size=128, step_bound=40)] * 2
    clean = cm.choose_mixed(segments=segs)
    assert "segmented" in clean.costs
    contended = cm.choose_mixed(segments=segs, contention_rate=0.5)
    assert contended.mode == "mixed"
    assert "segmented" not in contended.costs


def test_choose_batched_charges_uncached_compile():
    """An engine not yet built at this batch size costs an (amortized)
    XLA compile; a warm alternative should win until both are built."""
    cm = DispatchCostModel()
    cold = cm.choose_batched(batch=64, step_bound=40, batched_cached=True,
                             compiled_cached=False)
    assert cold.mode == "batched"
    warm = cm.choose_batched(batch=64, step_bound=40)
    assert warm.mode == "compiled"
    amortized = (EngineCost().compile_us
                 / EngineCost().compile_amortization)
    assert cold.costs["compiled"] == pytest.approx(
        warm.costs["compiled"] + amortized)


def test_choose_batched_double_buffer_crossover():
    """Long gather chains prefer the double-buffered compiled schedule;
    chains that fit in one chunk (no overlap to win, scheduling cost to
    lose) stay on the monolithic trace; contention excludes both."""
    cm = DispatchCostModel()
    long_ = cm.choose_batched(batch=256, step_bound=5 * 64 + 6,
                              chain_iters=64)
    assert long_.mode == "compiled_dbuf"
    assert long_.costs["compiled_dbuf"] < long_.costs["compiled"]
    short = cm.choose_batched(batch=256, step_bound=5 * 4 + 6,
                              chain_iters=4)
    assert short.mode == "compiled"
    assert short.costs["compiled_dbuf"] > short.costs["compiled"]
    no_chain = cm.choose_batched(batch=256, step_bound=40)
    assert "compiled_dbuf" not in no_chain.costs
    contended = cm.choose_batched(batch=256, step_bound=5 * 64 + 6,
                                  chain_iters=64,
                                  contention_rate=0.5)
    assert contended.mode == "batched"
    assert "compiled_dbuf" not in contended.costs


def test_observe_overlap_learns_ewma_term():
    """The overlap term adapts online: a measured pair where double-
    buffering hid most of the chain pulls the term up; decisions then
    price the dbuf path cheaper than before."""
    cm = DispatchCostModel()
    before = cm.cost.dbuf_overlap
    cost_before = cm.cost.compiled_dbuf_us(256, 5 * 64, 64)
    new = cm.observe_overlap(100.0, 20.0)     # 80% hidden
    assert new > before
    assert cm.cost.dbuf_overlap == new
    assert cm.cost.compiled_dbuf_us(256, 5 * 64, 64) < cost_before
    # degenerate observations leave the term untouched
    assert cm.observe_overlap(0.0, 10.0) == new
    # a pessimal pair (no hiding) pulls it down, clamped at 0
    worse = cm.observe_overlap(100.0, 100.0)
    assert 0.0 <= worse < new


def test_choose_placement_prices_single_as_best_local_dispatch():
    """The PR-4 scope gap: "single" used to be priced as the mixed
    engine only, so a low-entropy wave whose best local plan is
    segmented (big compiled per-op launches) was routed to the mesh
    prematurely.  With the dense plan's segment stats the single-chip
    side is the min of mixed and segmented and keeps the wave local."""
    cm = DispatchCostModel()
    # 4 big segments, total B=1024, long traces: segmented
    # crushes mixed locally, and sharding (collective tax per step)
    # beats *mixed* but not *segmented*
    segs = [SegmentStats(size=256, step_bound=60)] * 4
    kw = dict(batch=1024, n_devices=8, step_bound=60,
              batch_per_device=128)
    old = cm.choose_placement(**kw)                    # no segment stats
    assert old.mode == "sharded"                       # the old mispick
    new = cm.choose_placement(**kw, segments=segs)
    assert new.mode == "single"
    assert new.costs["single"] == new.costs["single_segmented"]
    assert new.costs["single"] < new.costs["sharded"]
    assert new.costs["single_mixed"] == old.costs["single"]
    # under contention segmentation is excluded (it reorders across
    # ops) and the serialized-scan terms dominate both sides
    cont = cm.choose_placement(**kw, segments=segs, contention_rate=0.5)
    assert "single_segmented" not in cont.costs
    assert cont.mode == "single"


def test_engine_cost_measured_adapts_launch_only():
    c = EngineCost.measured(reps=3)
    base = EngineCost()
    assert c.launch_us > 0
    # only the dispatch overhead adapts to the host; step constants keep
    # their calibration (so decisions shift with the launch/step ratio)
    assert c.vlane_us == base.vlane_us
    assert c.interp_step_us == base.interp_step_us


# ---------------------------------------------------------------------------
# Region views
# ---------------------------------------------------------------------------

def test_merge_tables_rejects_ambiguous_tenants():
    t = memory.packed_table([("x", 64)])
    with pytest.raises(ValueError, match="must not contain"):
        merge_tables([("a", t), ("a/b", memory.packed_table([("y", 64)]))])
    with pytest.raises(ValueError, match="duplicate tenant"):
        merge_tables([("a", t), ("a", memory.packed_table([("y", 64)]))])


def test_region_view_namespacing():
    a = memory.packed_table([("x", 64), ("y", 128)])
    b = memory.packed_table([("x", 256)])
    combined, views = merge_tables([("a", a), ("b", b)])
    va, vb = views["a"], views["b"]
    assert va["x"].size == 64 and vb["x"].size == 256
    assert va.rid("x") != vb.rid("x")
    assert combined[va.rid("x")].name == "a/x"
    assert sorted(va.names()) == ["a/x", "a/y"]
    assert len(va) == 2 and len(vb) == 1
    # grants built from a view cover only that tenant's regions
    ga = Grant.all_of(va, "a")
    assert ga.readable == {va.rid("x"), va.rid("y")}
    # views share the combined table's dense arrays (global rids)
    base_v, _, _ = va.as_arrays()
    base_c, _, _ = combined.as_arrays()
    assert np.array_equal(base_v, base_c)
    # a view writes land at the combined offsets
    mem = memory.make_pool(1, combined)
    memory.write_region(mem, vb, 0, "x", [7, 8, 9])
    r = combined["b/x"]
    assert list(mem[0, r.base:r.base + 3]) == [7, 8, 9]


# ---------------------------------------------------------------------------
# Registry control plane
# ---------------------------------------------------------------------------

def _tiny_program(name: str, rt) -> "OperatorBuilder":
    b = OperatorBuilder(name, n_params=0, regions=rt)
    b.ret()
    return b.build()


def test_registry_mode_validation():
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    op_id = reg.register("t", _tiny_program("p", rt))
    mem = memory.make_pool(1, rt)
    with pytest.raises(ValueError, match="unknown mode"):
        reg._invoke(op_id, mem, mode="batched")
    with pytest.raises(ValueError, match="unknown mode"):
        reg._invoke_batched(op_id, mem, [[]], mode="interp")
    with pytest.raises(ValueError, match="unknown mode"):
        reg._invoke_mixed([op_id], mem, [[]], mode="compiled")
    with pytest.raises(ValueError, match="unknown mode"):
        reg._invoke_batched(op_id, mem, [[]], mode="Auto")


def test_registry_duplicate_key_rejected():
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    reg.add_tenant(Grant.all_of(rt, "u"))
    reg.register("t", _tiny_program("p", rt))
    with pytest.raises(RegistrationError, match="already registered"):
        reg.register("t", _tiny_program("p", rt))
    # same name under a different tenant is a different key — fine
    reg.register("u", _tiny_program("p", rt))


def test_registry_op_table_capacity():
    """The 257th registration must be rejected — the hardware dispatch
    table has 256 entries."""
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    for i in range(isa.OP_TABLE_SIZE):
        reg.register("t", _tiny_program(f"p{i}", rt))
    assert len(reg) == isa.OP_TABLE_SIZE
    with pytest.raises(RegistrationError, match="table full"):
        reg.register("t", _tiny_program("one_too_many", rt))


def test_registry_instruction_store_capacity():
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))

    def big_program(name):
        b = OperatorBuilder(name, n_params=0, regions=rt)
        for _ in range(isa.INSTR_STORE_SIZE // 2 - 1):
            b.nop()
        b.ret()
        return b.build()

    reg.register("t", big_program("a"))
    reg.register("t", big_program("b"))
    with pytest.raises(RegistrationError, match="instruction store full"):
        reg.register("t", big_program("c"))


def test_invoke_mixed_validation_and_delegation():
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    b = OperatorBuilder("store7", n_params=1, regions=rt)
    b.store(b.param(0), "d", b.const(0))
    b.ret(b.param(0))
    op_id = reg.register("t", b.build())
    mem = memory.make_pool(1, rt)
    with pytest.raises(ValueError, match="does not match"):
        reg._invoke_mixed([op_id], mem, [[1], [2]])
    with pytest.raises(KeyError):
        reg._invoke_mixed([op_id, 99], mem, [[1], [2]])
    # single-op wave under "auto" delegates to the single-op dispatcher
    r_mixed = reg._invoke_mixed([op_id, op_id], mem, [[5], [6]],
                               mode="auto")
    r_batched = reg._invoke_batched(op_id, mem, [[5], [6]], mode="auto")
    assert np.array_equal(r_mixed.ret, r_batched.ret)
    assert np.array_equal(r_mixed.mem, r_batched.mem)


def test_store_ops_layout_matches_dispatch_table():
    """Concatenating store_ops() in op_id order reproduces the hardware
    dispatch table's start_pc entries — the invariant the mixed engine's
    merged store relies on."""
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    for i in range(5):
        b = OperatorBuilder(f"p{i}", n_params=0, regions=rt)
        for _ in range(i + 1):
            b.nop()
        b.ret()
        reg.register("t", b.build())
    table = reg.dispatch_table()
    off = 0
    for i, vop in enumerate(reg.store_ops()):
        assert table[i] == off
        off += vop.code.shape[0]
    assert np.all(table[5:] == -1)


def test_invoke_mixed_threads_contention_rate_to_segments():
    """A contended mixed wave dispatched as "segmented" must route every
    segment to the exact batched interpreter, not the compiled trace."""
    rt = memory.packed_table([("d", 64)])
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "t"))
    b1 = OperatorBuilder("sload", n_params=1, regions=rt)
    off = b1.const(0)
    b1.store(b1.param(0), "d", off)
    b1.ret(b1.load(b1.reg(), "d", off))
    id1 = reg.register("t", b1.build())
    b2 = OperatorBuilder("loader", n_params=0, regions=rt)
    b2.ret(b2.load(b2.reg(), "d", b2.const(0)))
    id2 = reg.register("t", b2.build())
    mem = memory.make_pool(1, rt)
    reg._invoke_mixed([id1, id2, id1], mem, [[5], [], [6]],
                     mode="segmented", contention_rate=0.9)
    assert reg.last_decision.mode == "batched"
    assert "compiled" not in reg.last_decision.costs
    # under "auto" the *wave-level* decision survives the nested
    # per-segment dispatches — that is what callers audit
    reg._invoke_mixed([id1, id2, id1], mem, [[5], [], [6]], mode="auto")
    assert reg.last_decision.mode in ("mixed", "segmented")
    assert reg.last_decision.entropy_bits > 0


def test_registry_last_decision_recorded():
    w = ops.GraphWalk(n_nodes=64, max_depth=8, reply_words=8 * 8)
    rt = w.regions()
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "alice"))
    op_id = reg.register("alice", w.build(rt, reply_param=True))
    mem = memory.make_pool(1, rt)
    order = w.populate(mem, rt)
    params = [[int(order[i]) * 8, 3, i * ops.NODE_WORDS] for i in range(4)]
    reg._invoke_batched(op_id, mem, params, mode="auto")
    assert reg.last_decision is not None
    assert reg.last_decision.mode in ("batched", "compiled")
    assert set(reg.last_decision.costs) >= {"batched"}
    # this wave is statically provable (disjoint affine reply windows,
    # graph is read-only), so the conflict proof discards the caller's
    # contention guess — the proof is a fact, the hint was an estimate
    reg._invoke_batched(op_id, mem, params, mode="auto",
                       contention_rate=0.9)
    assert reg.last_decision.static_noconflict
    assert reg.last_decision.contention_rate == 0.0
    # without the proof, the contention hint steers auto to the exact
    # interpreter, whose per-step conflict check serializes exactly
    reg.static_analysis = False
    reg._invoke_batched(op_id, mem, params, mode="auto",
                       contention_rate=0.9)
    assert not reg.last_decision.static_noconflict
    assert reg.last_decision.mode == "batched"
    reg.static_analysis = True
