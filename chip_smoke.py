"""Smoke test of the main path on a TPU, in one process.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the sharded phase only

Phase A (operators) sets up one ``TiaraEndpoint`` with one tenant per
stock operator (GraphWalk over a 2^22-node graph, PageTableWalk,
DistLock, PagedKVFetch, MoEExpertGather), posts one mixed stream through
a ``ServingLoop``, pumps and drains it, and checks every CQE against the
per-request ``pyvm`` oracle replayed on the same pool: ret, status and
steps per post, and the whole pool (every post's reply slot included)
word for word.

Phase B (model) serves InternLM2-1.8B at its published widths in bf16,
parameters drawn from ``--seed``, through ``ServingEngine`` with
``resolver="tiara"`` and checks its tokens against ``resolver="host"``,
the paged-attention kernel in the decode program and against its
float32 reference, and that no model program holds a 64-bit array.

Phase S (``--chips 4``) runs the phase-A mix homed over four device rows
with ``placement="sharded"`` on a four-chip mesh, and checks it against
``placement="single"`` and ``pyvm`` bit for bit.

Everything but the last line of standard output is informational.  The
last line is one JSON object, ``{"ok": true, "device": {...}}``, printed
only when every check passed.  Without a TPU the script exits non-zero
before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import re
import sys
import time
import traceback
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parent

GRAPH_NODES = 1 << 22          # 2^22 x 64 B nodes = 256 MiB of graph
POSTS_PER_OP = 256             # 5 operators -> 1280 posts
LOCK_PAIRS = 32                # (latch, state) pairs in the 64-word lock
WAVE = 5 * LOCK_PAIRS          # posts per wave: 32 of each operator, so
#                                the lock posts of one wave take disjoint
#                                pairs and the wave matches the oracle's
#                                one-at-a-time replay
MIN_CHECKED_PER_OP = 64
# One lockstep engine for the whole wave.  The cost model's "auto" picks
# "segmented" here: five compiled traces, which the v5e compiler takes
# about 400 s to build against about 50 s for the mixed engine.
MODE = "mixed"

MODEL = "internlm2-1.8b"
SLOTS = 8
MAX_SEQ = 2048
MAX_NEW = 32
PROMPT_LENS = (64, 512)        # shortest and longest prompt
# paged attention, bf16 kernel vs float32 reference: |out - ref| <=
# ATOL + RTOL |ref|.  bf16 keeps 8 significant bits, so rounding the
# output alone costs up to 2^-8 = 0.4 % of |out|; the rest is f32
# accumulation order.  1e-2 is 2.5 bf16 units.
ATTN_RTOL = ATTN_ATOL = 1e-2

_WIDE = re.compile(r"\b[fsu]64\[")      # a 64-bit array in HLO text


class Checks:
    """Named pass/fail records; a failed check fails the run."""

    def __init__(self) -> None:
        self.failed: List[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"  {'PASS' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def info(msg: str) -> None:
    print(f"  {msg}", flush=True)


# ---------------------------------------------------------------------------
# Phase A: the stock operator mix through a ServingLoop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Mix:
    ep: object
    posts: List[Tuple[str, str, List[int], int]]   # tenant, op, params,
    #                                                home


def build_mix(n_devices: int, graph_nodes: int, posts_per_op: int,
              seed: int) -> Mix:
    """One endpoint, one tenant per stock operator, every device row
    populated from ``seed``; the post stream takes operators in turn and
    homes post j of each operator on row j mod n_devices.  Every post
    that writes a reply has a reply slot of its own."""
    import numpy as np

    from repro.core import operators as ops
    from repro.core.endpoint import TiaraEndpoint

    gw = ops.GraphWalk(n_nodes=graph_nodes, max_depth=64,
                       reply_words=posts_per_op * ops.NODE_WORDS)
    ptw = ops.PageTableWalk(reply_pages=posts_per_op)
    lock = ops.DistLock()
    kv = ops.PagedKVFetch(n_blocks_pool=256, block_bytes=8192,
                          max_req_blocks=4, reply_slots=posts_per_op)
    moe = ops.MoEExpertGather(n_experts=64, max_k=4,
                              reply_slots=posts_per_op)
    layouts = [("graph", gw), ("ptw", ptw), ("lock", lock), ("kv", kv),
               ("moe", moe)]
    ep, sess = TiaraEndpoint.for_tenants(
        [(t, w.regions()) for t, w in layouts], n_devices=n_devices)
    op = {}
    for t, w in layouts:
        s = sess[t]
        prog = w.build(s.view) if t == "lock" \
            else w.build(s.view, reply_param=True)
        s.register(prog)
        op[t] = prog.name

    orders, vas = [], []
    for d in range(n_devices):
        orders.append(gw.populate(sess["graph"].pool, sess["graph"].view,
                                  device=d, seed=seed + d))
        vamap = ptw.populate(sess["ptw"].pool, sess["ptw"].view,
                             device=d, seed=seed + d)
        vas.append(sorted(vamap))
        kv.populate(sess["kv"].pool, sess["kv"].view, device=d,
                    seed=seed + d)
        kv.make_request(sess["kv"].pool, sess["kv"].view, [3, 9, 1, 200],
                        device=d)
        moe.populate(sess["moe"].pool, sess["moe"].view, device=d,
                     seed=seed + d)
        sess["moe"].write_region("expert_ids",
                                 np.asarray([5, 2, 9, 40], np.int64),
                                 device=d)

    stream: List[Tuple[str, List[int], int]] = []
    for j in range(posts_per_op):
        h = j % n_devices
        p = j % LOCK_PAIRS
        stream += [
            ("graph", [int(orders[h][(7 * j) % graph_nodes])
                       * ops.NODE_WORDS, j % 64, j * ops.NODE_WORDS], h),
            ("ptw", [int(vas[h][j % len(vas[h])]), j * ops.PAGE_WORDS], h),
            # latch 2p, state 2p+1, replicas on the next two rows
            ("lock", [2 * p, 2 * p + 1, 1000 + j, (h + 1) % n_devices,
                      2 * p + 1, (h + 2) % n_devices, 2 * p + 1], h),
            ("kv", [1 + j % 4, j * kv.max_req_blocks * kv.block_words], h),
            ("moe", [1 + j % 4, j * moe.max_k * moe.slab_words], h),
        ]
    return Mix(ep=ep, posts=[(t, op[t], params, h)
                             for t, params, h in stream])


@dataclasses.dataclass
class StreamResult:
    completions: list
    stats: object
    first_launch_s: float
    stream_s: float
    device_pool: str


def run_stream(mix: Mix, placement: str) -> StreamResult:
    """Submit every post, pump until the backlog is launched, drain."""
    import jax

    from repro.core.serving_loop import ServingConfig, ServingLoop

    ep = mix.ep
    loop = ServingLoop(ep, ServingConfig(
        ring_size=WAVE, max_pending=len(mix.posts), max_inflight_waves=2,
        mode=MODE, placement=placement))
    comps = [loop.submit(t, op, params, home=h)
             for t, op, params, h in mix.posts]
    t0 = time.perf_counter()
    first = None
    device_pool = "host-resident between waves"
    while loop.backlog:
        rep = loop.pump()
        if rep.launched and first is None:
            first = time.perf_counter() - t0
        if isinstance(ep.mem, jax.Array):
            device_pool = (f"{ep.mem.nbytes} bytes {ep.mem.dtype} "
                           f"{tuple(ep.mem.shape)} on "
                           f"{sorted(str(d) for d in ep.mem.devices())}")
    stats = loop.drain()
    return StreamResult(completions=comps, stats=stats,
                        first_launch_s=first or 0.0,
                        stream_s=time.perf_counter() - t0,
                        device_pool=device_pool)


def oracle_replay(mix: Mix, mem0, completions):
    """Replay every executed post on ``pyvm`` in launch order (wave, then
    arrival within the wave) from the pre-run pool ``mem0``; returns the
    per-post (ret, status, steps) and the final pool."""
    from repro.core import pyvm

    vops = mix.ep.registry.store_ops()
    mem = mem0.copy()
    out = {}
    for c in sorted(completions, key=lambda c: (c.event.wave, c.seq)):
        r = pyvm.run(vops[c.op_id], mix.ep.regions, mem, list(c.params),
                     home=c.home)
        out[c.seq] = (r.ret, r.status, r.steps)
    return out, mem


def check_stream(check: Checks, tag: str, mix: Mix, res: StreamResult,
                 oracle: Dict[int, tuple], oracle_mem) -> None:
    import numpy as np

    from repro.core import isa

    comps = res.completions
    st = res.stats
    bad = [c for c in comps if c.status != isa.STATUS_OK]
    check(f"{tag} every CQE STATUS_OK", not bad,
          f"{len(comps) - len(bad)}/{len(comps)} ok"
          + (f"; first bad: {bad[0].op_name} status {bad[0].status}"
             if bad else ""))
    terminal = st.executed + st.flushed + st.timed_out + st.rejected \
        + st.shed
    check(f"{tag} exactly one CQE per post",
          st.submitted == len(comps) == terminal == st.executed
          and all(c.done for c in comps),
          f"submitted {st.submitted} = executed {st.executed} + flushed "
          f"{st.flushed} + timed_out {st.timed_out} + rejected "
          f"{st.rejected} + shed {st.shed}")
    per_op: Dict[str, int] = {}
    mism = []
    for c in comps:
        got = (c.ret, c.status, c.steps)
        if oracle.get(c.seq) == got:
            per_op[c.op_name] = per_op.get(c.op_name, 0) + 1
        else:
            mism.append((c.seq, c.op_name, got, oracle.get(c.seq)))
    check(f"{tag} ret/status/steps == pyvm for every post", not mism,
          f"matched per operator {per_op}"
          + (f"; {len(mism)} mismatches, first {mism[0]}" if mism else ""))
    check(f"{tag} >= {MIN_CHECKED_PER_OP} checked posts per operator",
          len(per_op) == 5
          and min(per_op.values()) >= MIN_CHECKED_PER_OP, str(per_op))
    final = np.asarray(mix.ep.mem)
    diff = np.flatnonzero(final != oracle_mem) if final.shape == \
        oracle_mem.shape else np.asarray([-1])
    check(f"{tag} pool (all reply words) == pyvm", diff.size == 0,
          f"{final.nbytes} bytes compared"
          + (f"; {diff.size} words differ, first flat index {diff[0]}"
             if diff.size else ""))


def phase_operators(check: Checks, seed: int) -> None:
    import numpy as np

    print("[A] operator mix through TiaraEndpoint + ServingLoop",
          flush=True)
    t0 = time.perf_counter()
    mix = build_mix(1, GRAPH_NODES, POSTS_PER_OP, seed)
    mem0 = np.array(mix.ep.mem, copy=True)
    info(f"set-up {time.perf_counter() - t0:.1f} s; pool "
         f"{mem0.nbytes} bytes ({mem0.nbytes / 2**20:.0f} MiB), graph "
         f"{GRAPH_NODES} nodes; {len(mix.posts)} posts, waves of {WAVE}")
    res = run_stream(mix, "single")
    info(f"engine mode {MODE} (ServingConfig.mode)")
    info(f"pool on device: {res.device_pool}")
    info(f"first wave launch (compile included) {res.first_launch_s:.2f} s"
         f"; whole stream {res.stream_s:.2f} s")
    t0 = time.perf_counter()
    oracle, oracle_mem = oracle_replay(mix, mem0, res.completions)
    info(f"pyvm replay {time.perf_counter() - t0:.1f} s")
    check_stream(check, "[A]", mix, res, oracle, oracle_mem)

    # one more wave of the same composition, now warm
    from repro.core import isa
    from repro.core.serving_loop import ServingConfig, ServingLoop

    loop = ServingLoop(mix.ep, ServingConfig(ring_size=WAVE,
                                             max_pending=WAVE, mode=MODE))
    t0 = time.perf_counter()
    warm = [loop.submit(t, op, params, home=h)
            for t, op, params, h in mix.posts[:WAVE]]
    loop.pump()
    loop.drain()
    dt = time.perf_counter() - t0
    info(f"warm wave: {WAVE} posts submit->retire {dt * 1e3:.1f} ms "
         f"(host clock, pool copied back to host included)")
    check("[A] warm wave STATUS_OK",
          all(c.status == isa.STATUS_OK for c in warm))


# ---------------------------------------------------------------------------
# Phase B: InternLM2-1.8B through ServingEngine(resolver="tiara")
# ---------------------------------------------------------------------------

def phase_model(check: Checks, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_attention import paged_attention
    from repro.models import transformer as tf
    from repro.serving import ServingEngine

    print(f"[B] {MODEL} through ServingEngine", flush=True)
    cfg = get_config(MODEL)
    t0 = time.perf_counter()
    # one program draws every leaf on the device
    params = jax.jit(tf.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    info(f"{n_params} parameters ({cfg.param_dtype}) drawn from seed "
         f"{seed} in {time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(seed)
    lens = list(PROMPT_LENS) + sorted(
        int(x) for x in rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1,
                                     size=SLOTS - len(PROMPT_LENS)))
    prompts = [rng.integers(1, cfg.vocab, size=n).tolist() for n in lens]
    info(f"prompt lengths {lens}, max_new {MAX_NEW}")

    tokens: Dict[str, List[List[int]]] = {}
    for resolver in ("host", "tiara"):
        eng = ServingEngine(cfg, params, max_slots=SLOTS, max_seq=MAX_SEQ,
                            eos_id=-1, resolver=resolver)
        handles = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
        t0 = time.perf_counter()
        eng.step()                 # prefill every slot + first decode
        jax.block_until_ready(eng.caches)
        first = time.perf_counter() - t0
        tables = eng.block_tables.copy()
        lengths = eng.lengths.copy()
        t0 = time.perf_counter()
        steps = 0
        while not eng.finished():
            eng.step()
            steps += 1
        jax.block_until_ready(eng.caches)
        dt = time.perf_counter() - t0
        tokens[resolver] = [h.tokens for h in handles]
        info(f"resolver={resolver}: first step (8 prefills + decode, "
             f"compile included) {first:.1f} s; {steps} more decode "
             f"steps {dt:.2f} s ({dt / max(steps, 1) * 1e3:.1f} ms/step, "
             f"host clock)")
        check(f"[B] {resolver}: every sequence done with {MAX_NEW} tokens",
              all(h.ok and len(h.tokens) == MAX_NEW for h in handles))
        if resolver == "tiara":
            audit = eng.resolver_audit()
            info(f"tiara resolver audit: {audit}")
            _model_programs(check, eng, lens)
            _attention_vs_reference(check, eng, tables, lengths, seed,
                                    paged_attention, jnp)
        del eng
        gc.collect()
    same = tokens["host"] == tokens["tiara"]
    first_diff = next((i for i, (a, b) in enumerate(
        zip(tokens["host"], tokens["tiara"])) if a != b), None)
    check("[B] tiara tokens == host tokens", same,
          f"{sum(len(t) for t in tokens['tiara'])} tokens"
          + (f"; first differing sequence {first_diff}" if not same
             else ""))


def _model_programs(check: Checks, eng, lens: List[int]) -> None:
    t0 = time.perf_counter()
    dec = eng.compiled_hlo("decode")
    check("[B] decode HLO holds the paged-attention kernel",
          "tpu_custom_call" in dec)
    wide = sorted(set(_WIDE.findall(dec)))
    buckets = sorted({min(max(eng.cfg.page_size, 1 << (n - 1).bit_length()),
                          eng.max_seq) for n in lens})
    for b in buckets:
        pre = eng.compiled_hlo("prefill", bucket=b)
        check(f"[B] prefill({b}) HLO holds the flash-attention kernel",
              "tpu_custom_call" in pre)
        wide += sorted(set(_WIDE.findall(pre)))
    check("[B] no f64/s64/u64 array in decode or prefill "
          f"{buckets}", not wide, ", ".join(sorted(set(wide))))
    info(f"program audit {time.perf_counter() - t0:.1f} s")


def _attention_vs_reference(check: Checks, eng, tables, lengths, seed,
                            paged_attention, jnp) -> None:
    """One decode step's attention at real widths: the kernel on the
    engine's layer-0 pages vs the float32 reference on the same data."""
    import jax
    import numpy as np

    cfg = eng.cfg
    kv = eng.caches[0].paged
    k, v = kv.k_pages[0], kv.v_pages[0]
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (eng.max_slots, cfg.n_heads, cfg.head_dim),
                          jnp.float32).astype(k.dtype)
    bt = jnp.asarray(tables)
    ln = jnp.asarray(lengths)
    lowered = paged_attention.lower(q, k, v, bt, ln).as_text()
    check("[B] paged_attention(impl='auto') lowers to the kernel",
          "tpu_custom_call" in lowered)
    out = np.asarray(paged_attention(q, k, v, bt, ln), np.float32)
    ref = np.asarray(paged_attention(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32), bt, ln,
                                     impl="xla"))
    err = np.abs(out - ref)
    bound = ATTN_ATOL + ATTN_RTOL * np.abs(ref)
    check("[B] paged-attention kernel == f32 reference",
          bool(np.all(np.isfinite(out))) and bool(np.all(err <= bound)),
          f"max |err| {err.max():.3e}, max |ref| {np.abs(ref).max():.3e}, "
          f"tolerance {ATTN_ATOL} + {ATTN_RTOL}|ref|, lengths "
          f"{lengths.tolist()}")


# ---------------------------------------------------------------------------
# Phase S: the mix homed over four rows, sharded vs single vs pyvm
# ---------------------------------------------------------------------------

def phase_sharded(check: Checks, n_chips: int, seed: int) -> None:
    import numpy as np

    print(f"[S] operator mix over {n_chips} rows: placement sharded vs "
          f"single vs pyvm", flush=True)
    results = {}
    for placement in ("single", "sharded"):
        t0 = time.perf_counter()
        mix = build_mix(n_chips, GRAPH_NODES, POSTS_PER_OP, seed)
        mem0 = np.array(mix.ep.mem, copy=True)
        info(f"{placement}: set-up {time.perf_counter() - t0:.1f} s; pool "
             f"{mem0.nbytes} bytes over {n_chips} rows")
        res = run_stream(mix, placement)
        info(f"{placement}: pool on device: {res.device_pool}")
        info(f"{placement}: first wave launch (compile included) "
             f"{res.first_launch_s:.2f} s; whole stream "
             f"{res.stream_s:.2f} s")
        if placement == "single":
            t0 = time.perf_counter()
            oracle, oracle_mem = oracle_replay(mix, mem0, res.completions)
            info(f"pyvm replay {time.perf_counter() - t0:.1f} s")
        check_stream(check, f"[S] {placement}", mix, res, oracle,
                     oracle_mem)
        results[placement] = (
            [(c.seq, c.ret, c.status, c.steps) for c in res.completions],
            np.asarray(mix.ep.mem))
        del mix, res
        gc.collect()
    (a, ma), (b, mb) = results["single"], results["sharded"]
    check("[S] sharded == single, every post and the whole pool",
          a == b and np.array_equal(ma, mb))


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A and B; 4: the sharded phase only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: src/repro is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform} devices)",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()       # before the first compile
    print(f"device {dev.device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)

    check = Checks()
    t_all = time.perf_counter()
    if args.chips == 1:
        phases = [lambda: phase_operators(check, args.seed),
                  lambda: phase_model(check, args.seed)]
    else:
        phases = [lambda: phase_sharded(check, args.chips, args.seed)]
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:       # record, run the next phase, fail the run
            traceback.print_exc()
            check("phase raised", False)
        info(f"phase time {time.perf_counter() - t0:.1f} s")
    print(f"total {time.perf_counter() - t_all:.1f} s; "
          f"{len(check.failed)} failed checks", flush=True)
    if check.failed:
        print(f"chip_smoke: failed: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
