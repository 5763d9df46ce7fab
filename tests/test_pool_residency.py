"""The pool stays on the device across split-phase waves.

Retiring a wave brings back each post's per-lane result (ret, status,
steps, regs, fault), never the pool.  Region reads move only their
words; control-path writes pull the pool to the host once and the next
doorbell uploads it once.  ``pool_pulls`` / ``pool_pushes`` audit both
crossings, and the replays below hold every CQE and the final pool to
the ``pyvm`` oracle.
"""

import jax
import numpy as np
import pytest

from repro.core import memory, pyvm
from repro.core.endpoint import TiaraEndpoint
from repro.core.program import OperatorBuilder
from repro.core.serving_loop import ServingConfig, ServingLoop

N_TENANTS = 3
REPLY_SLOTS = 256


def _layout():
    return memory.packed_table([("data", 64), ("reply", REPLY_SLOTS)])


def _chain_op(rt):
    """reply[p2] = reply[p0] + data[p1]: each post reads a slot an
    earlier post wrote, so the pool carries state from wave to wave."""
    b = OperatorBuilder("chain", n_params=3, regions=rt)
    x, y = b.reg(), b.reg()
    b.load(x, "reply", b.param(0))
    b.load(y, "data", b.param(1))
    b.add(x, x, y)
    b.store(x, "reply", b.param(2))
    b.ret(x)
    return b.build()


def _connect():
    named = [(f"t{i}", _layout()) for i in range(N_TENANTS)]
    ep, sessions = TiaraEndpoint.for_tenants(named)
    for s in sessions.values():
        s.register(_chain_op(s.view))
        s.write_region("data", np.arange(10, 74, dtype=np.int64))
    return ep, [sessions[f"t{i}"] for i in range(N_TENANTS)]


def _replay(ep, mem0, completions):
    """Per-request pyvm replay in retirement order from ``mem0``."""
    vops = ep.registry.store_ops()
    mem = mem0.copy()
    for c in sorted(completions, key=lambda c: (c.event.wave, c.seq)):
        r = pyvm.run(vops[c.op_id], ep.regions, mem, list(c.params),
                     home=c.home)
        assert (c.ret, c.status, c.steps) == (r.ret, r.status, r.steps), c
    return mem


def _on_device(ep):
    return isinstance(ep.mem, jax.Array)


@pytest.mark.parametrize("mode", ["mixed", "segmented"])
def test_closed_loop_keeps_pool_on_device(mode):
    """A closed split-phase loop through ServingLoop: after the first
    wave's upload no whole pool crosses either way, and every CQE and
    the final pool match the oracle replayed in retirement order."""
    ep, sessions = _connect()
    mem0 = ep.host_mem().copy()
    pulls0 = ep.pool_pulls
    loop = ServingLoop(ep, ServingConfig(ring_size=6, max_pending=4,
                                         max_inflight_waves=2, mode=mode))
    fresh = {s.tenant: 1 for s in sessions}
    done, after_first = [], None

    def submit(s, src):
        dst = fresh[s.tenant]
        fresh[s.tenant] += 1
        loop.submit(s.tenant, "chain", [src, dst % 64, dst])

    for _ in range(4):
        for s in sessions:
            submit(s, 0)
    while len(done) < 20 * N_TENANTS:
        if loop.pump(force=True).launched and after_first is None:
            after_first = (ep.pool_pulls, ep.pool_pushes)
            assert after_first == (pulls0, 1)
        got = [c for s in sessions for c in s.poll_cq()]
        if not got:
            ep.wait_any()
            got = [c for s in sessions for c in s.poll_cq()]
        for c in got:
            done.append(c)
            # the source slot was written by a post that has retired
            submit(c.session, c.params[2])
        assert _on_device(ep)
    loop.drain()
    done += [c for s in sessions for c in s.poll_cq()]
    assert len({c.event.wave for c in done}) >= 6
    assert (ep.pool_pulls, ep.pool_pushes) == after_first
    assert _on_device(ep)
    expect = _replay(ep, mem0, done)
    assert np.array_equal(ep.host_mem(), expect)
    assert ep.pool_pulls == pulls0 + 1


def test_read_region_moves_only_the_region():
    """A region read on a device-resident pool (landed or still in
    flight) returns the host view's words and leaves the pool on the
    device."""
    ep, (s0, s1, _) = _connect()
    for i in range(8):
        s0.post("chain", [0, i, 1 + i])
        s1.post("chain", [0, 63 - i, 100 + i])
    ep.doorbell(mode="mixed", wait=False)
    # in flight: the read is ordered after the wave that writes it
    first = s0.read_region("reply", offset=1, count=8)
    assert list(first) == list(range(10, 18))
    ep.wait_all()
    assert _on_device(ep)
    pulls = ep.pool_pulls
    host = np.asarray(ep.mem)
    for s in (s0, s1):
        base = s.view["reply"].base
        for offset, count in ((0, 16), (100, 8), (37, 1), (0, None)):
            n = REPLY_SLOTS - offset if count is None else count
            got = s.read_region("reply", offset=offset, count=count)
            assert got.dtype == np.int64
            assert np.array_equal(got, host[0, base + offset:
                                            base + offset + n])
    assert list(s1.read_region("reply", offset=100, count=8)) == \
        [10 + 63 - i for i in range(8)]
    assert _on_device(ep)
    assert ep.pool_pulls == pulls
    with pytest.raises(ValueError):
        s0.read_region("reply", offset=REPLY_SLOTS - 2, count=3)
    with pytest.raises(ValueError):
        s0.read_region("reply", offset=-1, count=2)
    with pytest.raises(IndexError):
        s0.read_region("reply", device=1, count=2)


def test_write_region_between_waves_costs_one_round_trip():
    """A control-path write between split-phase waves pulls the pool
    once and the next doorbell uploads it once; that wave sees the
    write."""
    ep, (s0, _, _) = _connect()
    s0.post("chain", [0, 0, 1])
    ep.doorbell(mode="mixed", wait=False)
    ep.wait_all()
    assert _on_device(ep)
    pulls, pushes = ep.pool_pulls, ep.pool_pushes
    s0.write_region("reply", [1000], offset=5)
    assert (ep.pool_pulls, ep.pool_pushes) == (pulls + 1, pushes)
    c = s0.post("chain", [5, 2, 6])
    ep.doorbell(mode="mixed", wait=False)
    assert (ep.pool_pulls, ep.pool_pushes) == (pulls + 1, pushes + 1)
    assert c.wait().ret == 1000 + 12
    assert _on_device(ep)
    assert list(s0.read_region("reply", offset=5, count=2)) == [1000, 1012]
    assert (ep.pool_pulls, ep.pool_pushes) == (pulls + 1, pushes + 1)


def test_blocking_doorbell_returns_the_pool_each_wave():
    """A blocking doorbell hands the pool back as a host array, so each
    wave pulls it and the next one uploads it again."""
    ep, (s0, _, _) = _connect()
    for i in range(3):
        s0.post("chain", [i, i, i + 1])
        assert ep.doorbell(mode="mixed") == 1
        assert isinstance(ep.mem, np.ndarray)
        assert (ep.pool_pulls, ep.pool_pushes) == (i + 1, i + 1)
    assert list(s0.read_region("reply", count=4)) == [0, 10, 21, 33]
