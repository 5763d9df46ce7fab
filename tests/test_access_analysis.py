"""Registration-time access analysis (``core/access``): symbolic
footprints, static wave conflict proofs, the proof-gated sweep-skip
fast path, and the widened superoperator matcher.

The invariants under test:

1. Footprints are per-site symbolic offsets — affine in params,
   trip-scaled loop windows with static caps, or top — and the edge
   cases stay sound *windows*, never silently wrong: jump-out-of-loop
   (the Fig. 5 lock break) joins to an interval, a dynamic
   (``FLAG_MREG``) loop is bounded by its static cap, and an MREG body
   degrades to a cap-bounded window rather than ⊤.
2. ``prove_wave_noconflict`` is *sound*: it never clears a wave whose
   exact dynamic read/write sets conflict cross-lane (seeded sweep
   always; hypothesis when installed), and a cleared wave executes
   bit-identically to the sequential ``pyvm`` oracle on every engine
   (dense mixed, segmented, compiled, sharded).
3. The proof is *useful*: provably-disjoint waves do prove, reach the
   engines as a separately-keyed sweep-skip variant, replace the
   caller's contention guess in the cost model, and override a slot's
   learned conflict EWMA at wave formation.
4. The widened superoperator matcher (scatter-reduce, map, zip-with)
   is exact vs ``pyvm`` including faults, scatter-reduce fusion stays
   gated on a no-conflict build, and the registry surfaces footprints,
   matches, and near-miss reasons.
"""

import jax
import numpy as np
import pytest

from repro.core import access, isa, memory, pyvm, vm
from repro.core import compile as tc
from repro.core.costmodel import DispatchCostModel, SegmentStats
from repro.core.endpoint import TiaraEndpoint
from repro.core.isa import Alu
from repro.core.memory import Grant
from repro.core.program import OperatorBuilder
from repro.core.registry import OperatorRegistry
from repro.core.serving_loop import ServingConfig, ServingLoop, VirtualClock
from repro.core.verifier import VerificationError, verify

N_DEV = len(jax.devices())

two_devices = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs 2 devices (run under XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _table():
    return memory.packed_table([("src", 1024), ("reply", 1024),
                                ("acc", 256)])


# ---------------------------------------------------------------------------
# Operator families with exact dynamic-footprint companions.  The
# companions mirror what feeds the runtime sweep (``vm.lane_intervals``):
# word accesses at masked in-region addresses, atomics as writes whatever
# the compare outcome.  No family writes ``src``, so every companion is
# exact regardless of wave interleaving.
# ---------------------------------------------------------------------------

def _op_pair(rt):
    """Writes reply[p0] and reply[p0+1] — pure affine footprint."""
    b = OperatorBuilder("pair", n_params=2, regions=rt)
    t = b.reg()
    b.alu(t, b.param(1), Alu.ADD, 7)
    b.store(t, "reply", b.param(0))
    b.store(t, "reply", b.param(0), disp=1)
    b.ret(t)
    return b.build()


def _op_window(rt):
    """MREG loop, trip p2 capped at 8: reads src[p0+t], writes
    reply[p0+t] — trip-scaled window footprint."""
    b = OperatorBuilder("window", n_params=3, regions=rt)
    i, v = b.reg(), b.reg()
    b.alu(i, b.param(0), Alu.ADD, 0)
    with b.loop((b.param(2), 8)):
        b.load(v, "src", i)
        b.store(v, "reply", i)
        b.alu(i, i, Alu.ADD, 1)
    b.ret(v)
    return b.build()


def _op_chase(rt):
    """Writes reply[src[p0]] — data-dependent offset, ⊤ footprint."""
    b = OperatorBuilder("chase", n_params=1, regions=rt)
    v = b.reg()
    b.load(v, "src", b.param(0))
    b.store(v, "reply", v)
    b.ret(v)
    return b.build()


def _op_atom(rt):
    """CAA on acc[p0] — one-word atomic footprint."""
    b = OperatorBuilder("atom", n_params=3, regions=rt)
    old = b.reg()
    b.caa(old, "acc", b.param(0), b.param(1), b.param(2))
    b.ret(old)
    return b.build()


FAMILIES = ("pair", "window", "chase", "atom")


def _registry(rt, *, n_devices=1, **kw):
    reg = OperatorRegistry(rt, n_devices=n_devices, **kw)
    reg.add_tenant(Grant.all_of(rt, "t"))
    ids = {}
    for fam, build in (("pair", _op_pair), ("window", _op_window),
                       ("chase", _op_chase), ("atom", _op_atom)):
        ids[fam] = reg.register("t", build(rt))
    return reg, ids


def _touched(fam, rt, mem0, params, home):
    """Exact dynamic (read_cells, write_cells) of one lane, as the
    runtime sweep would see them: sets of (device, pool_addr)."""
    src, rep, acc = rt["src"], rt["reply"], rt["acc"]
    p = list(params) + [0] * 8
    if fam == "pair":
        w = {(home, rep.base + (p[0] & rep.mask)),
             (home, rep.base + ((p[0] + 1) & rep.mask))}
        return set(), w
    if fam == "window":
        trip = min(max(p[2], 0), 8)
        r = {(home, src.base + ((p[0] + t) & src.mask))
             for t in range(trip)}
        w = {(home, rep.base + ((p[0] + t) & rep.mask))
             for t in range(trip)}
        return r, w
    if fam == "chase":
        cell = src.base + (p[0] & src.mask)
        v = int(mem0[home, cell])
        return {(home, cell)}, {(home, rep.base + (v & rep.mask))}
    assert fam == "atom"
    return set(), {(home, acc.base + (p[0] & acc.mask))}


def _would_conflict(lanes):
    """Would the dynamic sweep ever flag this wave?  True iff some
    lane's writes intersect another lane's reads or writes."""
    for i in range(len(lanes)):
        ri, wi = lanes[i]
        for j in range(i):
            rj, wj = lanes[j]
            if (wi & (rj | wj)) or (wj & ri):
                return True
    return False


def _draw_wave(rng, disjoint):
    """One 4-lane wave: op family, params, home per lane.  With
    ``disjoint`` the lanes are slot-strided far apart (should prove);
    otherwise params collide freely (must never prove unsoundly)."""
    fams, params, homes = [], [], []
    for lane in range(4):
        fam = FAMILIES[int(rng.integers(len(FAMILIES)))]
        if disjoint and fam == "chase":
            fam = "pair"  # ⊤ footprints never prove
        home = int(rng.integers(2))
        if disjoint:
            base = 64 * lane
            p = {"pair": [base, 3], "window": [base, 0, 5],
                 "atom": [32 * lane, 0, 1]}[fam]
            home = lane % 2
        else:
            p = {"pair": [int(rng.integers(1024)), 3],
                 "window": [int(rng.integers(1024)), 0,
                            int(rng.integers(12))],
                 "chase": [int(rng.integers(1024))],
                 "atom": [int(rng.integers(256)), 0, 1]}[fam]
        fams.append(fam)
        params.append(p)
        homes.append(home)
    return fams, params, homes


def _soundness_round(reg, op_ids, rt, mem0, fams, params, homes):
    ids = [op_ids[f] for f in fams]
    verdict = reg.prove_wave_noconflict(ids, params, homes, n_devices=2)
    lanes = [_touched(f, rt, mem0, p, h)
             for f, p, h in zip(fams, params, homes)]
    if verdict:
        assert not _would_conflict(lanes), (
            f"UNSOUND: proof cleared a conflicting wave {fams} {params}")
    return verdict


def test_soundness_seeded_sweep():
    rt = _table()
    reg, ids = _registry(rt, n_devices=2)
    rng = np.random.default_rng(0)
    mem0 = rng.integers(0, 2048, size=(2, rt.pool_words)).astype(np.int64)
    verdicts = []
    for k in range(120):
        fams, params, homes = _draw_wave(rng, disjoint=(k % 3 == 0))
        verdicts.append(
            _soundness_round(reg, ids, rt, mem0, fams, params, homes))
    # non-vacuity: the proof must both clear and refuse across the sweep
    assert any(verdicts) and not all(verdicts)


def test_soundness_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    rt = _table()
    reg, ids = _registry(rt, n_devices=2)
    rng0 = np.random.default_rng(7)
    mem0 = rng0.integers(0, 2048, size=(2, rt.pool_words)).astype(np.int64)

    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(0, 2**32 - 1), disjoint=st.booleans())
    def prop(seed, disjoint):
        rng = np.random.default_rng(seed)
        fams, params, homes = _draw_wave(rng, disjoint)
        _soundness_round(reg, ids, rt, mem0, fams, params, homes)

    prop()


# ---------------------------------------------------------------------------
# Bit-parity of statically-cleared waves across every engine
# ---------------------------------------------------------------------------

def _pyvm_replay(reg, rt, mem, ids, params, homes):
    out = []
    for i, p, h in zip(ids, params, homes):
        out.append(pyvm.run(reg[i].verified, rt, mem, p, home=h))
    return out


def test_proven_wave_parity_dense_and_segmented():
    rt = _table()
    reg, ids = _registry(rt)
    fams = ["pair", "window", "atom", "pair"]
    wave = [ids[f] for f in fams]
    params = [[0, 3], [128, 0, 5], [64, 0, 9], [300, 4]]
    rng = np.random.default_rng(1)
    mem0 = rng.integers(0, 512, size=(1, rt.pool_words)).astype(np.int64)

    oracle_mem = mem0.copy()
    oracle = _pyvm_replay(reg, rt, oracle_mem, wave, params, [0] * 4)

    for mode in ("mixed", "segmented"):
        r = reg._invoke_mixed(wave, mem0.copy(), params, mode=mode)
        assert reg.last_noconflict is True
        assert np.array_equal(np.asarray(r.mem), oracle_mem), mode
        assert [int(x) for x in r.ret] == [o.ret for o in oracle], mode
        assert [int(x) for x in r.status] == [o.status for o in oracle]
        assert [int(x) for x in r.steps] == [o.steps for o in oracle]
    # the sweep-skip variant is what actually got built: it is cached
    # under its own engine key
    assert vm.mixed_engine_cached(reg.store_ops(), rt, 1, 4,
                                  static_noconflict=True)


def test_proven_wave_parity_compiled():
    rt = _table()
    reg, ids = _registry(rt)
    # single-op MREG wave, slots strided past the 8-iteration cap
    params = [[32 * i, 0, 6] for i in range(4)]
    rng = np.random.default_rng(2)
    mem0 = rng.integers(0, 512, size=(1, rt.pool_words)).astype(np.int64)
    oracle_mem = mem0.copy()
    oracle = _pyvm_replay(reg, rt, oracle_mem,
                          [ids["window"]] * 4, params, [0] * 4)
    r = reg._invoke_batched(ids["window"], mem0.copy(), params,
                            mode="compiled")
    assert reg.last_noconflict is True
    assert np.array_equal(np.asarray(r.mem), oracle_mem)
    assert [int(x) for x in r.ret] == [o.ret for o in oracle]
    assert [int(x) for x in r.steps] == [o.steps for o in oracle]


@two_devices
def test_proven_wave_parity_sharded():
    rt = _table()
    reg, ids = _registry(rt, n_devices=2)
    fams = ["pair", "window", "pair", "atom"]
    wave = [ids[f] for f in fams]
    params = [[0, 3], [128, 0, 5], [0, 9], [64, 0, 1]]
    homes = [0, 0, 1, 1]
    rng = np.random.default_rng(3)
    mem0 = rng.integers(0, 512, size=(2, rt.pool_words)).astype(np.int64)
    oracle_mem = mem0.copy()
    oracle = _pyvm_replay(reg, rt, oracle_mem, wave, params, homes)
    r = reg._invoke_mixed(wave, mem0.copy(), params, homes=homes,
                          mode="mixed", placement="sharded")
    assert reg.last_noconflict is True
    assert np.array_equal(np.asarray(r.mem), oracle_mem)
    assert [int(x) for x in r.ret] == [o.ret for o in oracle]
    assert [int(x) for x in r.status] == [o.status for o in oracle]


def test_unproven_wave_keeps_sweep_and_stays_exact():
    """A colliding wave must not prove, and the sweep fallback keeps the
    deterministic serialized semantics (matches the dense mixed engine's
    own contract — here vs sequential replay on *non*-colliding params
    and simple overlap on colliding ones)."""
    rt = _table()
    reg, ids = _registry(rt)
    wave = [ids["pair"], ids["pair"]]
    params = [[10, 1], [11, 2]]  # reply[10,11] vs reply[11,12]: overlap
    assert reg.prove_wave_noconflict(wave, params, 0) is False
    mem0 = np.zeros((1, rt.pool_words), dtype=np.int64)
    r = reg._invoke_mixed(wave, mem0, params, mode="mixed")
    assert reg.last_noconflict is False
    # lockstep: per step the lanes' words are disjoint (lane 0 touches
    # reply[10] while lane 1 touches reply[11], then 11 vs 12), so the
    # contended word retires in *step* order — lane 0's second store
    # lands last.  The refused proof is conservatively sound: its
    # whole-execution spans overlap even though no single step does.
    rep = rt["reply"]
    assert int(np.asarray(r.mem)[0, rep.base + 11]) == 1 + 7


# ---------------------------------------------------------------------------
# Footprint edge cases (verifier interaction)
# ---------------------------------------------------------------------------

def _verified(rt, build):
    return verify(build(rt), grant=Grant.all_of(rt, "t"), regions=rt)


def test_jump_out_of_loop_joins_to_window():
    """The Fig. 5 lock-break shape: a conditional jump out of a loop.
    The post-loop state is the join of every exit — the footprint must
    widen to the full window, not track one path."""
    rt = _table()

    def build(rt):
        b = OperatorBuilder("lockbreak", n_params=2, regions=rt)
        i, t = b.reg(), b.reg()
        b.alu(i, b.param(0), Alu.ADD, 0)
        out = b.mklabel()
        with b.loop(4):
            b.alu(t, b.param(1), Alu.ADD, 1)
            b.store(t, "reply", i)
            b.jump(out, i, Alu.EQ, b.param(1))   # break mid-window
            b.alu(i, i, Alu.ADD, 1)
        b.bind(out)
        b.store(t, "acc", i)                     # post-join access
        b.ret(t)
        return b.build()

    v = _verified(rt, build)
    fp = v.footprint
    assert fp is not None and fp.exact  # joined, not ⊤
    # full static window overlaps => must refuse; far apart => proves
    reg, _ = _registry(rt)
    op = reg.register("t", build(rt))
    assert reg.prove_wave_noconflict(
        [op, op], [[0, 999], [2, 998]], 0) is False
    assert reg.prove_wave_noconflict(
        [op, op], [[0, 999], [64, 998]], 0) is True


def test_dynamic_loop_cap_bounds_window():
    """A FLAG_MREG loop's window is bounded by the *static cap* even
    when the trip register is huge — lanes strided by the cap prove."""
    rt = _table()
    reg, ids = _registry(rt)
    op = ids["window"]
    huge = 1 << 40
    # the trip symbol spans [0, m] inclusive (one symbol covers both the
    # body iterations and the post-loop cursor), so the provable stride
    # is cap+1 — what matters is that a 2^40 trip register still proves
    assert reg.prove_wave_noconflict(
        [op, op], [[0, 0, huge], [16, 0, huge]], 0) is True
    assert reg.prove_wave_noconflict(
        [op, op], [[0, 0, huge], [4, 0, huge]], 0) is False


def test_mreg_loop_degrades_to_window_not_top():
    rt = _table()
    v = _verified(rt, _op_window)
    fp = v.footprint
    assert fp is not None
    assert fp.exact, "MREG body must stay a cap-bounded window, not ⊤"
    assert len(fp.aux_trips) == 1 and fp.aux_trips[0][1] == 8
    d = access.describe_footprint(fp, rt)
    assert "t0" in d and "⊤" not in d


def test_data_dependent_offset_is_top():
    rt = _table()
    v = _verified(rt, _op_chase)
    assert v.footprint is not None and not v.footprint.exact
    assert "⊤" in access.describe_footprint(v.footprint, rt)


def test_verifier_diagnostics_carry_operator_name():
    rt = _table()
    grant = Grant.of("t", readable=[rt.rid("src")], writable=[])

    def build(rt):
        b = OperatorBuilder("nogrant", n_params=1, regions=rt)
        v = b.reg()
        b.load(v, "src", b.param(0))
        b.store(v, "reply", b.param(0))
        b.ret(v)
        return b.build()

    with pytest.raises(VerificationError) as ei:
        verify(build(rt), grant=grant, regions=rt)
    assert ei.value.errors, "expected at least one diagnostic"
    for err in ei.value.errors:
        assert err.startswith("nogrant: pc "), err


# ---------------------------------------------------------------------------
# Registry surface: toggle, dump, cross-op fusion
# ---------------------------------------------------------------------------

def test_static_analysis_toggle_disables_proofs():
    rt = _table()
    reg, ids = _registry(rt, static_analysis=False)
    assert reg.prove_wave_noconflict(
        [ids["pair"], ids["pair"]], [[0, 1], [64, 2]], 0) is False


def test_dump_reports_footprints_and_superops():
    rt = _table()
    reg, _ = _registry(rt)
    d = reg.dump()
    assert "footprint:" in d
    assert "⊤" in d                       # chase's top shows up
    assert "superop near-miss: pc" in d   # window's non-chain loop


def test_cross_op_fusion_of_identical_programs():
    """Two tenants registering the same program get distinct op_ids;
    the segmented path coalesces their segments into one launch."""
    rt = _table()
    reg = OperatorRegistry(rt)
    reg.add_tenant(Grant.all_of(rt, "a"))
    reg.add_tenant(Grant.all_of(rt, "b"))
    opa = reg.register("a", _op_pair(rt))
    opb = reg.register("b", _op_pair(rt))
    wave = [opa, opb, opa, opb]
    params = [[64 * i, i] for i in range(4)]
    mem0 = np.zeros((1, rt.pool_words), dtype=np.int64)
    oracle_mem = mem0.copy()
    oracle = _pyvm_replay(reg, rt, oracle_mem, wave, params, [0] * 4)
    r = reg._invoke_mixed(wave, mem0, params, mode="segmented")
    assert reg.last_fused_groups == [[opa, opb]]
    assert np.array_equal(np.asarray(r.mem), oracle_mem)
    assert [int(x) for x in r.ret] == [o.ret for o in oracle]


# ---------------------------------------------------------------------------
# Cost model: a proof replaces the contention guess
# ---------------------------------------------------------------------------

def test_choose_batched_proof_overrides_contention():
    m = DispatchCostModel()
    guess = m.choose_batched(batch=64, step_bound=32, contention_rate=0.9)
    assert "compiled" not in guess.costs  # guess blocks the trace
    proven = m.choose_batched(batch=64, step_bound=32, contention_rate=0.9,
                              static_noconflict=True)
    assert proven.static_noconflict and proven.contention_rate == 0.0
    assert "compiled" in proven.costs


def test_choose_mixed_proof_enables_segmented():
    m = DispatchCostModel()
    segs = [SegmentStats(size=32, step_bound=16)] * 2
    guess = m.choose_mixed(segments=segs, contention_rate=0.5)
    assert "segmented" not in guess.costs
    proven = m.choose_mixed(segments=segs, contention_rate=0.5,
                            static_noconflict=True)
    assert "segmented" in proven.costs and proven.static_noconflict


def test_sharded_cost_drops_collective_under_proof():
    m = DispatchCostModel()
    base = m.cost.sharded_us(64, 4, 32, 0.0, batch_per_device=16)
    nc = m.cost.sharded_us(64, 4, 32, 0.0, batch_per_device=16,
                           noconflict=True)
    assert nc < base  # the footprint all_gather left the step


# ---------------------------------------------------------------------------
# Endpoint + serving loop integration
# ---------------------------------------------------------------------------

def test_endpoint_last_noconflict_audit():
    layout = memory.packed_table([("src", 64), ("reply", 64)])
    ep, sessions = TiaraEndpoint.for_tenants(
        [("t0", layout), ("t1", layout)])

    def build(rt):
        b = OperatorBuilder("w1", n_params=2, regions=rt)
        t = b.reg()
        b.alu(t, b.param(1), Alu.ADD, 0)
        b.store(t, "reply", b.param(0))
        b.ret(t)
        return b.build()

    for s in sessions.values():
        s.register(build(s.view))
    assert ep.last_noconflict is None
    c0 = sessions["t0"].post("w1", [1, 11])
    c1 = sessions["t1"].post("w1", [1, 22])   # distinct regions: disjoint
    ep.doorbell(mode="mixed")
    assert ep.last_noconflict is True
    assert c0.ok and c1.ok


def test_wave_profile_proof_overrides_learned_contention():
    layout = memory.packed_table([("src", 64), ("reply", 64)])
    vc = VirtualClock()
    ep, sessions = TiaraEndpoint.for_tenants(
        [("t0", layout), ("t1", layout)], clock=vc, sleep=vc.sleep)

    def build(rt):
        b = OperatorBuilder("w1", n_params=2, regions=rt)
        t = b.reg()
        b.alu(t, b.param(1), Alu.ADD, 0)
        b.store(t, "reply", b.param(0))
        b.ret(t)
        return b.build()

    for s in sessions.values():
        s.register(build(s.view))
    loop = ServingLoop(ep, ServingConfig(ring_size=4))
    # poison the EWMA: the slots look contended from history
    loop.submit("t0", "w1", [1, 5], contention=1.0)
    loop.submit("t1", "w1", [2, 6], contention=1.0)
    picked = [q[0] for q in loop._pending.values()]
    ids = sorted({c.op_id for c in picked})
    assert max(ep.cost_model.conflict_hint(i) for i in ids) > 0.0
    _, _, contention = loop._wave_profile(picked)
    assert contention == 0.0, \
        "static proof must override the learned contention guess"


# ---------------------------------------------------------------------------
# Widened superoperator matcher: scatter-reduce, map, zip-with
# ---------------------------------------------------------------------------

def _sr_table():
    return memory.packed_table([("src", 256), ("acc", 256)])


def _op_scatter_reduce(rt, stride=2, cap=8, dev=isa.DEV_LOCAL):
    b = OperatorBuilder("scatred", n_params=3, regions=rt)
    i, j, v, old = b.reg(), b.reg(), b.reg(), b.reg()
    b.alu(i, b.param(0), Alu.ADD, 0)
    b.alu(j, b.param(1), Alu.ADD, 0)
    with b.loop(cap):
        b.load(v, "src", i)
        b.caa(old, "acc", j, b.param(2), v, dev=dev)
        b.alu(j, j, Alu.ADD, stride)
        b.alu(i, i, Alu.ADD, 1)
    b.ret(old)
    return b.build()


def test_scatter_reduce_matched_and_exact():
    rt = _sr_table()
    v = verify(_op_scatter_reduce(rt), grant=Grant.all_of(rt, "t"),
               regions=rt)
    rep = tc.superop_report(v)
    assert ("scatter_reduce", 2) in rep["matched"]
    rng = np.random.default_rng(4)
    mem0 = rng.integers(0, 64, size=(1, rt.pool_words)).astype(np.int64)
    params = [[0, 0, 0], [16, 64, 5]]
    oracle_mem = mem0.copy()
    oracle = [pyvm.run(v, rt, oracle_mem, p) for p in params]
    for noconflict in (True, False):   # fused and unfused both exact
        r = tc.invoke_compiled(v, rt, mem0.copy(), params,
                               noconflict=noconflict)
        assert np.array_equal(np.asarray(r.mem), oracle_mem), noconflict
        assert [int(x) for x in r.ret] == [o.ret for o in oracle]
        assert [int(x) for x in r.steps] == [o.steps for o in oracle]


def test_scatter_reduce_fault_parity():
    """A CAA landing on a failed device faults mid-chain: the fused
    schedule must retire the same registers, steps, and fault record as
    the interpreter."""
    rt = _sr_table()
    v = verify(_op_scatter_reduce(rt, dev=1), grant=Grant.all_of(rt, "t"),
               regions=rt)
    rng = np.random.default_rng(5)
    mem0 = rng.integers(0, 64, size=(2, rt.pool_words)).astype(np.int64)
    params = [[0, 0, 0]]
    oracle_mem = mem0.copy()
    o = pyvm.run(v, rt, oracle_mem, params[0], failed={1})
    assert o.status == isa.STATUS_PROT_FAULT
    r = tc.invoke_compiled(v, rt, mem0.copy(), params, failed={1},
                           noconflict=True)
    assert int(r.status[0]) == o.status
    assert np.array_equal(np.asarray(r.mem), oracle_mem)
    f = r.fault_at(0)
    assert f is not None and (f.pc, f.opcode) == (o.fault.pc,
                                                  o.fault.opcode)
    assert int(r.steps[0]) == o.steps
    assert [int(x) for x in r.regs[0]] == o.regs


def test_map_and_zip_loops_exact():
    rt = memory.packed_table([("a", 256), ("b", 256), ("dst", 256)])

    def map_op(rt):
        b = OperatorBuilder("maploop", n_params=2, regions=rt)
        i, j, x, c = b.reg(), b.reg(), b.reg(), b.reg()
        b.alu(i, b.param(0), Alu.ADD, 0)
        b.alu(j, b.param(1), Alu.ADD, 0)
        with b.loop(8):
            b.load(x, "a", i)
            b.alu(c, x, Alu.MUL, 3)
            b.store(c, "dst", j)
            b.alu(j, j, Alu.ADD, 1)
            b.alu(i, i, Alu.ADD, 1)
        b.ret(c)
        return b.build()

    def zip_op(rt):
        bb = OperatorBuilder("ziploop", n_params=3, regions=rt)
        i, j, x, y, c = bb.reg(), bb.reg(), bb.reg(), bb.reg(), bb.reg()
        bb.alu(i, bb.param(0), Alu.ADD, 0)
        bb.alu(j, bb.param(1), Alu.ADD, 0)
        with bb.loop((bb.param(2), 8)):
            bb.load(x, "a", i)
            bb.load(y, "b", i)
            bb.alu(c, x, Alu.ADD, y)
            bb.store(c, "dst", j)
            bb.alu(j, j, Alu.ADD, 1)
            bb.alu(i, i, Alu.ADD, 1)
        bb.ret(c)
        return bb.build()

    rng = np.random.default_rng(6)
    for build, kind, params in (
            (map_op, "map_loop", [[0, 0], [16, 32]]),
            (zip_op, "zip_loop", [[0, 0, 5], [16, 32, 99]])):
        v = verify(build(rt), grant=Grant.all_of(rt, "t"), regions=rt)
        rep = tc.superop_report(v)
        assert any(k == kind for k, _ in rep["matched"]), (kind, rep)
        mem0 = rng.integers(0, 64, size=(1, rt.pool_words)).astype(np.int64)
        oracle_mem = mem0.copy()
        oracle = [pyvm.run(v, rt, oracle_mem, p) for p in params]
        r = tc.invoke_compiled(v, rt, mem0.copy(), params)
        assert np.array_equal(np.asarray(r.mem), oracle_mem), kind
        assert [int(x) for x in r.ret] == [o.ret for o in oracle]
        assert [int(x) for x in r.steps] == [o.steps for o in oracle]


def test_gather_chain_near_miss_reason():
    rt = _table()
    v = verify(_op_window(rt), grant=Grant.all_of(rt, "t"), regions=rt)
    instrs = isa.decode_program(v.code)
    g, reason = tc.match_gather_chain_ex(instrs, v.loops[0])
    assert g is None and "5-instruction chain shape" in reason
    assert tc.superop_report(v)["near_miss"].startswith("pc ")
