"""Quickstart: write a Tiara operator, register it on an endpoint, post
work to your queue pair, ring the doorbell, poll the completion.

    PYTHONPATH=src python examples/quickstart.py

The surface mirrors an RNIC: a ``TiaraEndpoint`` owns the memory pool
and the dispatch table; ``connect()`` gives each tenant a ``Session``
(queue pair) with its regions and grant wired automatically;
``Session.post`` enqueues pre-registered operator invocations; one
``doorbell()`` drains every session's posts as a single batched wave.
"""

import jax

from repro.core import costmodel as cm
from repro.core import faults
from repro.core import simulator as sim
from repro.core import operators as ops
from repro.core import serving_loop as serving
from repro.core.endpoint import EndpointError, TiaraEndpoint
from repro.core.frontend import compile_source
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    # A disaggregated memory node: a graph region and a reply region.
    w = ops.GraphWalk(n_nodes=4096, max_depth=64)

    # 1. Stand up the endpoint (it owns the pool) and connect: the
    #    tenant's regions, view, and grant are wired in one call.  With
    #    more than one device row the pool can later shard over a mesh
    #    (step 6); under XLA_FLAGS=--xla_force_host_platform_device_
    #    count=8 the mesh is 4 real host devices wide.
    n_dev = max(1, min(4, len(jax.devices())))
    ep, sessions = TiaraEndpoint.for_tenants(
        [("quickstart", w.regions())], n_devices=n_dev)
    sess = sessions["quickstart"]

    # 2. Write the operator in the restricted source subset (paper §3.3).
    walk_src = '''
def walk(start, depth):
    cur = start
    for _ in bounded(depth, 64):
        cur = load("graph", cur + 1)     # the loaded value IS the next
    memcpy("reply", 0, "graph", cur, 8)  # address: register-chained loads
    return load("graph", cur)
'''
    program = compile_source(walk_src, regions=sess.view)
    print("compiled operator:")
    print(program.disassemble(), "\n")

    # 3. Register it: compile -> static verification against the
    #    session's grant -> op_id in the endpoint's dispatch table.
    op_id = sess.register(program)
    vop = ep.registry[op_id].verified
    print(f"registered as op {op_id}; proven step bound = "
          f"{vop.step_bound}, loop depth = {vop.max_loop_depth}")

    #    Registration also derives the operator's symbolic access
    #    footprint (core/access.py).  This walk chases loaded addresses,
    #    so its footprint is ⊤ ("could touch anywhere in the region")
    #    and its waves keep the runtime conflict sweep.  Operators with
    #    affine footprints get whole waves proven conflict-free at plan
    #    time instead — the sweep (and, sharded, a collective per step)
    #    is then compiled out; `ep.last_noconflict` after a doorbell
    #    says which way the last wave went, and
    #    `OperatorRegistry(static_analysis=False)` turns the proofs off.
    print("access analysis:", ep.registry[op_id].describe_analysis(),
          "\n")

    #    ... and its line-rate certificate (core/wcet.py): sound static
    #    upper bounds on worst-case cycles, word/wire traffic, and
    #    per-resource occupancy, with the statically predicted
    #    bottleneck.  The registry rejects operators whose certificate
    #    exceeds its Budget (eBPF-style, naming the offending pc), and
    #    the serving loop fail-fasts posts whose deadline is already
    #    below the certified WCET.
    cert = ep.registry[op_id].certificate
    print("line-rate certificate:", cert.describe())
    hot = cert.hottest("cycles")
    print(f"hottest site: pc {hot.pc} {hot.op} x{hot.count} "
          f"({hot.cycles:.0f} worst-case cycles)\n")

    # 4. Populate the memory node and post work to the queue pair.  The
    #    doorbell drains the send queue as one wave; completions land in
    #    the session's completion queue.
    order = w.populate(sess.pool, sess.view)
    start, depth = int(order[0]) * 8, 24
    completion = sess.post("walk", [start, depth])
    ep.doorbell()
    (done,) = sess.poll_cq()
    assert done is completion and completion.done
    expect = w.reference(order, int(order[0]), depth)
    print(f"walk(depth={depth}) -> {completion.result()} "
          f"(reference {expect}, steps {completion.steps})")
    assert completion.result() == expect

    # ... or let the handle flush for you: result() rings the doorbell
    # if the post is still outstanding.
    assert sess.post("walk", [start, 12]).result() == \
        w.reference(order, int(order[0]), 12)

    # 5. What did it cost?  Cycle-level NIC timing vs one-sided RDMA
    #    (Session.trace replays the invocation on the pyvm oracle).
    trace = sess.trace("walk", [start, depth]).trace
    ts = sim.simulate_task(vop, trace)
    print(f"\nTiara:  {ts.latency_us:6.2f} us  (1 round trip + "
          f"{depth} local DMA hops)")
    print(f"RDMA:   {cm.rdma_chain_latency_us(depth):6.2f} us  "
          f"({depth} dependent round trips)")
    print(f"speedup: {cm.rdma_chain_latency_us(depth) / ts.latency_us:.2f}x"
          f"  (paper: 2.85x at depth 10)")

    # 6. Sharded placement: the pool's device rows shard over a mesh and
    #    each device executes the posts whose `home` it owns — placement
    #    is a doorbell concern, the posts don't change.  Every wave is
    #    bit-identical to single-chip execution (and to the pyvm
    #    oracle), whatever the placement.
    orders = [w.populate(sess.pool, sess.view, device=d, seed=d)
              for d in range(n_dev)]
    wave = [sess.post("walk", [int(orders[d][0]) * 8, 12], home=d)
            for d in range(n_dev)]
    ep.doorbell(placement="sharded")
    print(f"\nsharded wave over {n_dev} device(s):")
    for d, c in enumerate(wave):
        expect = w.reference(orders[d], int(orders[d][0]), 12)
        assert c.result() == expect
        print(f"  home {d}: walk(depth=12) -> {c.ret}  (reference ok)")

    # 7. Split-phase pipelining: doorbell(wait=False) *launches* a wave
    #    and returns an in-flight WaveHandle immediately — post the next
    #    wave while the first is still computing (the pool dependency
    #    chains through XLA's async dispatch), then retire both with one
    #    wait_all().  Completions still arrive per-session FIFO, and
    #    each carries a CompletionEvent with its retire timestamp.
    wave1 = [sess.post("walk", [start, d]) for d in (6, 18)]
    h1 = ep.doorbell(wait=False)              # launched, NOT retired
    wave2 = [sess.post("walk", [start, d]) for d in (30, 42)]
    ep.doorbell(wait=False)                   # pipelined behind wave 1
    assert not wave1[0].done and ep.in_flight == 4
    n = ep.wait_all()                         # retires both, wave order
    print(f"\npipelined two-wave step: {n} completions retired "
          f"(wave {h1.wave_id} first)")
    for c, d in zip(wave1 + wave2, (6, 18, 30, 42)):
        assert c.result() == w.reference(orders[0], int(orders[0][0]), d)
        print(f"  walk(depth={d}) -> {c.ret}  "
              f"(wave {c.event.wave}, retired at {c.event.retired_at:.3f})")

    # 8. Fault model (RNIC semantics).  Every engine runs with runtime
    #    protection on: a wild pointer, out-of-region window, or access
    #    to a failed device halts JUST that lane with
    #    STATUS_PROT_FAULT, suppresses all its writes, and the CQE
    #    carries FaultInfo(pc, opcode, addr, device).  Like a QP, the
    #    owning session enters an error state — later posts retire
    #    STATUS_FLUSHED without executing — until reset().  Here we
    #    tear one next-pointer via the declarative fault-injection
    #    harness (`core/faults.py`; plans compose with `+`):
    ep.inject(faults.corrupt_words(
        [(0, sess.view["graph"].base + start + 1, -999_999)]))
    torn = sess.post("walk", [start, 4])
    ep.doorbell()
    assert torn.faulted and sess.in_error
    print(f"\ntorn pointer -> {torn.fault}")
    flushed = sess.post("walk", [start, 4])     # QP in error: flushed
    assert flushed.flushed
    try:
        torn.result()                           # result() surfaces it
    except EndpointError as e:
        print(f"result() raised: {e}")
    sess.reset()                                # error state is sticky
    w.populate(sess.pool, sess.view, device=0, seed=0)   # heal the ring
    healed = sess.post("walk", [start, 12])
    ep.doorbell()
    assert healed.ok
    print(f"after reset + repair: walk(depth=12) -> {healed.result()}")

    # 9. Overload-safe serving.  Production callers don't ring the
    #    doorbell by hand: `ServingLoop` wraps the split-phase surface
    #    with admission control (per-tenant token buckets + weighted
    #    fair queueing), a continuous batcher (ring on size, head age,
    #    or cost-model launch efficiency), bounded in-flight waves,
    #    per-post deadlines, and load shedding.  Every submitted post
    #    retires exactly one CQE: executed, or STATUS_EAGAIN
    #    (reject/shed), STATUS_TIMEOUT (expired before launch),
    #    STATUS_FLUSHED (QP in error).  On a VirtualClock the whole
    #    run — including the injected overload below — is
    #    deterministic.
    vc = serving.VirtualClock()
    ep2, tenants = TiaraEndpoint.for_tenants(
        [("gold", w.regions()), ("econ", w.regions())],
        clock=vc, sleep=vc.sleep)
    starts, refs = {}, {}
    for name, s in tenants.items():
        s.register(compile_source(walk_src, regions=s.view))
        torder = w.populate(s.pool, s.view)
        starts[name] = int(torder[0]) * 8
        refs[name] = torder
    loop = serving.ServingLoop(
        ep2,
        serving.ServingConfig(ring_size=4, ring_age_s=0.002,
                              max_inflight_waves=2, max_pending=8,
                              shed_watermark=12,
                              default_deadline_s=0.05,
                              opportunistic_poll=False),
        qos={"gold": serving.TenantQoS(weight=2.0),
             "econ": serving.TenantQoS(weight=1.0, rate=300.0,
                                       burst=2)})
    # the injected overload: every wave is slowed by 20 ms of NIC
    # delay and "econ" is stalled outright for 60 ms — longer than
    # the 50 ms deadline, so its queued posts age out deterministically
    ep2.inject(faults.delay_waves(0.02) + faults.stall_tenant("econ", 0.06))
    posts = []
    for i in range(24):
        tenant = "gold" if i % 3 else "econ"
        depth = 6 + (i % 4)
        posts.append((tenant, depth,
                      loop.submit(tenant, "walk",
                                  [starts[tenant], depth])))
        loop.pump()
    loop.drain()
    ep2.clear_faults()
    st = loop.stats
    print(f"\nserving under injected overload ({st.submitted} posts):")
    print(f"  executed {st.executed} (ok {st.ok}), timed out "
          f"{st.timed_out}, rejected {st.rejected}, shed {st.shed}")
    # exactly one terminal outcome per submitted post ...
    assert st.submitted == (st.executed + st.flushed + st.timed_out
                            + st.rejected + st.shed)
    # ... the fabric kept serving, and the overload actually bit
    assert st.executed > 0 and st.timed_out + st.rejected + st.shed > 0
    for tenant, depth, c in posts:
        if c.ok:
            assert c.ret == w.reference(
                refs[tenant], int(refs[tenant][0]), depth)

    # 10. End-to-end disaggregated decode.  The serving engine's
    #     "tiara" resolver puts the whole stack behind a model: each
    #     decode lane is a session (queue pair) whose block table and
    #     KV-pool descriptors live on the endpoint, and every decode
    #     step posts a PagedKVFetch per active sequence through a
    #     ServingLoop — the operator's remote-reply MEMCPY streams the
    #     resolved block-table row to the client device the next
    #     decode consumes.  Output is bit-identical to the local
    #     resolver; the INDIGO-style re-homing sweep migrates hot
    #     regions toward their accessors while it serves.
    import jax
    from repro.configs import get_config, reduce_config
    from repro.models import transformer as tf
    from repro.serving import ServingEngine

    cfg = reduce_config(get_config("tiny-lm"))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[5, 9, 13, 2], [3, 1, 4, 1, 5]]
    outs = {}
    for resolver in ("host", "tiara"):
        eng = ServingEngine(cfg, params, max_slots=2, max_seq=64,
                            temperature=0.0, eos_id=-1,
                            resolver=resolver, n_homes=2,
                            placement="auto", rehome_every=2)
        handles = [eng.submit(p, max_new=4) for p in prompts]
        outs[resolver] = eng.run_to_completion()
        assert all(h.ok for h in handles)
        if resolver == "tiara":
            aud = eng.resolver_audit()
            print(f"\n2-session tiara-resolved decode: "
                  f"{sum(len(v) for v in outs['tiara'].values())} tokens "
                  f"over {aud['waves']:.0f} fabric waves, "
                  f"{aud['rehomes']:.0f} rehomes, cross-device words "
                  f"{aud['cross_device_words']:.0f}")
    assert outs["tiara"] == outs["host"], "disaggregated decode diverged"
    print("tiara resolver output is bit-identical to host resolve")


if __name__ == "__main__":
    main()
