"""A run with the timed path broken underneath must come out not
correct: each fault a cell can have, planted in the program, with the
rest of the run (traffic, window, reference, comparison) as it is."""

import dataclasses

import numpy as np
import pytest

from small import run_small

FABRIC = "paper-mix.qd320"
DECODE = "internlm2-1.8b-tiara"


def test_sound_runs_are_correct():
    for cell in (FABRIC, DECODE):
        res = run_small(cell)
        assert res["correct"], res["checks"]
        assert res["failed"] == 0 and res["window_compiles"] == 0


def test_fabric_answer_altered_where_produced(monkeypatch):
    from repro.core import vm

    real = vm.materialize_result

    def altered(res):
        out = real(res)
        ret = np.array(out.ret)
        ret[0] += 1
        return dataclasses.replace(out, ret=ret)

    monkeypatch.setattr(vm, "materialize_result", altered)
    res = run_small(FABRIC)
    assert not res["correct"] and res["checks"]["posts_wrong"]["value"] > 0


def test_fabric_pool_word_altered_where_produced(monkeypatch):
    """One reply word of the MoE tenant flipped in the pool each wave
    retires into."""
    from repro.core.endpoint import TiaraEndpoint

    real = TiaraEndpoint._retire

    def altered(self, handle):
        real(self, handle)
        if isinstance(self.mem, np.ndarray):
            mem = self.mem.copy()
            mem[0, self.regions["moe/reply"].base] ^= 1
            self.mem = mem

    monkeypatch.setattr(TiaraEndpoint, "_retire", altered)
    res = run_small(FABRIC)
    assert not res["correct"]
    assert res["checks"]["pool_words_wrong"]["value"] > 0


def test_fabric_half_of_each_wave_left_out(monkeypatch):
    from repro.core.endpoint import TiaraEndpoint

    real = TiaraEndpoint.doorbell

    def half(self, **kw):
        for s in self._sessions.values():
            s._sq = s._sq[:(len(s._sq) + 1) // 2]
        return real(self, **kw)

    monkeypatch.setattr(TiaraEndpoint, "doorbell", half)
    res = run_small(FABRIC)
    assert not res["correct"]
    assert res["checks"]["posts_missing"]["value"] > 0


def test_decode_token_altered_where_produced(monkeypatch):
    from repro.serving import engine

    real = engine.sample_tokens

    def altered(logits, key, temperature=0.0, top_k=None):
        out = np.array(real(logits, key, temperature, top_k))
        out[0] = (out[0] + 1) % np.asarray(logits).shape[-1]
        return out

    monkeypatch.setattr(engine, "sample_tokens", altered)
    res = run_small(DECODE)
    assert not res["correct"]
    assert res["checks"]["served_logit_gap_max"]["value"] > \
        res["checks"]["served_logit_gap_max"]["limit"]


def test_decode_half_of_the_batch_left_out(monkeypatch):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._resolve_block_tables

    def half(self, slots):
        bt = real(self, slots)
        bt[self.max_slots // 2:] = self.scratch_page
        return bt

    monkeypatch.setattr(ServingEngine, "_resolve_block_tables", half)
    res = run_small(DECODE)
    assert not res["correct"]
    assert res["checks"]["served_logit_gap_max"]["value"] > \
        res["checks"]["served_logit_gap_max"]["limit"]


@pytest.mark.parametrize("cell", [FABRIC, DECODE])
def test_control_is_not_correct(cell):
    """The control in the program's place: the reference with one stated
    guarantee broken (the operator mix), or computed in float8 (the
    model).  It must fail the comparison that sound runs pass."""
    import run
    from small import spec

    s = spec(cell)
    driver = run.load_module(run.BENCH / "drivers"
                             / f"{s['config']['driver']}.py")
    c = driver.build(s["config"], s["traffic"], 2**31 + 11, 1)
    c.window(2.0)
    c.release()
    program, _, _ = c.check()
    control, _, _ = (c.check(fp8=True) if cell == DECODE
                     else c.check(control=True))
    assert all(v <= lim for v, lim in program.values()), program
    assert any(v > lim for v, lim in control.values()), control


FOUR = "tiara-paper-mix-4node"


def test_sharded_exchange_between_chips_left_out(monkeypatch):
    """The sharded engine's window writes applied only where the writer
    runs: a lock's replicas on the other rows never receive its state.
    Needs four devices (XLA_FLAGS=--xla_force_host_platform_device_count=4
    on the CPU)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    from jax import numpy as jnp

    from repro.core import vm

    def local_only(self, mem, dev, phys, val, live):
        mine = live & (dev == self.me)[:, None]
        return mem.at[jnp.where(mine, jnp.clip(phys, 0, self.P - 1),
                                self.P)].set(val, mode="drop")

    monkeypatch.setattr(vm._ShardOps, "writev_win", local_only)
    # build the broken engine in a cache of its own, dropped afterwards
    monkeypatch.setattr(vm, "_VM_CACHE", {})
    assert run_small(FOUR)["correct"] is False


def test_sharded_sound_run_is_correct():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    assert run_small(FOUR)["correct"] is True
