"""The reduction from a trace to per-layer numbers, on a small trace
with answers worked out by hand, and on an excerpt recorded on a v5e."""

import json
from pathlib import Path

import pytest

import run
import timeline as tr

MS = 1_000_000
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return run.load_module(run.BENCH / "layers" / f"{name}.py").reduce


@pytest.fixture
def two_waves():
    """A 100 ms window on one chip: two wave programs of 30 ms (10-40,
    40-70, the second overlapped by 5 ms of a copy), idle 0-10 and
    75-100, host spans pump (0-12) and poll_cq (70-100)."""
    ops = [(0, "%while.1 while", 10 * MS, 40 * MS),
           (0, "%fusion.2 fusion", 12 * MS, 20 * MS),
           (0, "%while.1 while", 40 * MS, 70 * MS),
           (0, "%copy.3 copy", 68 * MS, 75 * MS)]
    modules = [(0, "jit_run(1)", 10 * MS, 40 * MS),
               (0, "jit_run(1)", 40 * MS, 70 * MS)]
    spans = [("bench.pump", 0, 12 * MS), ("bench.poll_cq", 70 * MS, 100 * MS)]
    return tr.Trace(window=(0, 100 * MS), chips=[0], ops=ops,
                    modules=modules, spans=spans)


def test_interval_arithmetic():
    assert tr.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 11) == \
        [(1, 4), (5, 11)]
    assert tr.gaps([(1, 4), (5, 11)], 0, 12) == [(0, 1), (4, 5), (11, 12)]
    assert tr.total([(1, 4), (5, 11)]) == 9


def test_busy_idle_and_breakdown(two_waves):
    assert tr.busy_s(two_waves) == pytest.approx(0.065)
    assert tr.idle_share(two_waves, 0) == pytest.approx(0.35)
    b = tr.breakdown(two_waves)
    assert b["device_ops"][0] == ["%while.1 while", pytest.approx(0.06)]
    assert b["idle_gaps"] == [["bench.poll_cq", pytest.approx(0.025)],
                              ["bench.pump", pytest.approx(0.010)]]


def test_fabric_readers(two_waves):
    run_info = {"wave_bytes": [819_000, 819_000], "peaks": PEAKS}
    assert reader("fabric.wave_device_ms")(two_waves, run_info) == \
        pytest.approx(30.0)
    # 35 ms idle over two waves
    assert reader("fabric.host_gap_ms")(two_waves, run_info) == \
        pytest.approx(17.5)
    # 819 kB in 30 ms at 819 GB/s: 1e-6 s of 0.03 s
    assert reader("fabric.wave_roofline")(two_waves, run_info) == \
        pytest.approx(100 * 1e-6 / 0.03)
    assert reader("fabric.device_idle_share")(two_waves, run_info) == \
        pytest.approx(35.0)
    # one chip: no collectives to read
    assert reader("fabric.collective_ms_per_wave")(two_waves, run_info) \
        is None


def test_readers_return_nothing_without_their_program(two_waves):
    empty = tr.Trace(window=(0, 100 * MS), chips=[0], ops=[], modules=[],
                     spans=[])
    info = {"wave_bytes": [1], "peaks": PEAKS, "steps": [([], [3])],
            "decode_bytes": [1], "kv_bytes": [1], "flops": 0}
    for name in ("fabric.wave_device_ms", "fabric.wave_roofline",
                 "fabric.host_gap_ms", "decode.step_device_ms",
                 "decode.step_roofline", "decode.paged_attention_roofline",
                 "decode.fabric_device_ms", "decode.mfu"):
        assert reader(name)(empty, info) is None


def test_decode_readers():
    """Three decode runs of 10 ms (each holding 2 ms of the kernel), one
    prefill of 20 ms under the same program name pattern, and one
    resolver wave of 3 ms per step, in a 100 ms window."""
    ops, modules = [], []
    for i in range(3):
        s = 10 * MS + 25 * i * MS
        modules += [(0, "jit__lambda(7)", s, s + 10 * MS),
                    (0, "jit_run(9)", s - 3 * MS, s)]
        ops += [(0, "%fusion.1 fusion", s, s + 10 * MS),
                (0, "%paged_attention.2 = bf16[32,16,128] custom-call(%x)",
                 s + 1 * MS, s + 3 * MS),
                (0, "%while.3 while", s - 3 * MS, s)]
    modules.append((0, "jit__lambda(8)", 80 * MS, 100 * MS))
    ops.append((0, "%fusion.4 fusion", 80 * MS, 100 * MS))
    t = tr.Trace(window=(0, 100 * MS), chips=[0], ops=ops, modules=modules,
                 spans=[])
    info = {"steps": [([], [10] * 32)] * 3, "peaks": PEAKS,
            "decode_bytes": [8.19e9 * 1e-3] * 3, "kv_bytes": [819e3] * 3,
            "flops": 197e12 * 0.1 * 0.25}
    assert reader("decode.step_device_ms")(t, info) == pytest.approx(10.0)
    assert reader("decode.fabric_device_ms")(t, info) == pytest.approx(3.0)
    # 8.19e6 bytes in 10 ms against 819 GB/s
    assert reader("decode.step_roofline")(t, info) == \
        pytest.approx(100 * 8.19e6 / 0.01 / 819e9)
    assert reader("decode.paged_attention_roofline")(t, info) == \
        pytest.approx(100 * 1e-6 / 2e-3)
    assert reader("decode.mfu")(t, info) == pytest.approx(25.0)
    assert reader("decode.device_idle_share")(t, info) == \
        pytest.approx(100 - 59)


RECORDED = Path(__file__).with_name("recorded_decode_step.json")


def test_recorded_excerpt_reduces_to_its_numbers():
    """11 ms of a traced internlm2-1.8b.decode32 run on one v5e: the end
    of the resolver's VM wave, the host's gap, and the start of the
    decode program."""
    t = tr.Trace.from_json(json.loads(RECORDED.read_text()))
    want = json.loads(RECORDED.with_suffix(".expected").read_text())
    assert tr.busy_s(t) == pytest.approx(want["busy_s"], rel=1e-12)
    b = tr.breakdown(t)
    assert b["idle_gaps"] == want["breakdown"]["idle_gaps"]
    assert [[n, pytest.approx(d, rel=1e-12)] for n, d in b["device_ops"]] \
        == want["breakdown"]["device_ops"]
    wave = tr.module_runs(t, 0, tr.FABRIC_WAVE_PROGRAM)
    decode = tr.most_runs(t, 0, tr.MODEL_PROGRAM)
    assert wave == [(62_000_000, 63_383_792)]
    assert decode == [(71_866_701, 73_000_000)]
    gap = b["idle_gaps"][0]
    assert gap[0] == "bench.engine_step"
    assert gap[1] == pytest.approx((71_866_701 - 63_383_792) * 1e-9,
                                   abs=2e-6)
    lo, hi = t.window
    assert tr.total(tr.busy(t, 0)) + tr.total(
        tr.gaps(tr.busy(t, 0), lo, hi)) == hi - lo
