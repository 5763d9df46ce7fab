"""The operator mix through ``TiaraEndpoint`` and ``ServingLoop``.

One tenant per stock operator on one endpoint; each tenant's client
keeps a fixed number of posts outstanding (a closed loop, as an RDMA
client keeps a queue depth) and posts the next one as soon as it polls
a completion.  The loop forms waves and the VM engine runs them against
the pool on the device.

Host spans mark every call the client makes into the system:
``bench.submit``, ``bench.pump``, ``bench.poll_cq``, ``bench.wait_any``
and ``bench.drain``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

from jax.profiler import TraceAnnotation

import generate
from reference import fabric as ref
from work import fabric as work

_STATUS_OK = 0


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 n_chips: int) -> None:
        from repro.core import operators as ops
        from repro.core.endpoint import TiaraEndpoint
        from repro.core.serving_loop import ServingConfig, ServingLoop

        self.cfg, self.traffic = cfg, traffic
        n_dev = cfg["memory_nodes"]
        slots = cfg["reply_slots"]
        g, p, k, m = cfg["graph"], cfg["ptw"], cfg["kv"], cfg["moe"]
        layouts = [
            ("graph", ops.GraphWalk(n_nodes=g["n_nodes"],
                                    max_depth=g["max_depth"],
                                    reply_words=slots * ref.NODE_WORDS)),
            ("ptw", ops.PageTableWalk(fanout=p["fanout"],
                                      n_pages=p["n_pages"],
                                      reply_pages=slots)),
            ("lock", ops.DistLock(max_retries=cfg["lock"]["max_retries"])),
            ("kv", ops.PagedKVFetch(n_blocks_pool=k["n_blocks"],
                                    block_bytes=k["block_bytes"],
                                    max_req_blocks=k["max_req_blocks"],
                                    reply_slots=slots)),
            ("moe", ops.MoEExpertGather(
                n_experts=m["n_experts"], max_k=m["max_k"],
                slab_words=ref.slab_words(cfg), reply_slots=slots)),
        ]
        self.ep, self.sessions = TiaraEndpoint.for_tenants(
            [(t, w.regions()) for t, w in layouts], n_devices=n_dev)
        self.op: Dict[str, str] = {}
        for t, w in layouts:
            prog = (w.build(self.sessions[t].view) if t == "lock" else
                    w.build(self.sessions[t].view, reply_param=True))
            self.sessions[t].register(prog)
            self.op[t] = prog.name
        self.rows = [ref.make_row(cfg, seed, d) for d in range(n_dev)]
        for d, row in enumerate(self.rows):
            for tenant, regions in row.regions().items():
                for region, words in regions.items():
                    self.sessions[tenant].write_region(region, words,
                                                       device=d)
        self.posts = generate.fabric_posts(cfg, traffic, self.rows, seed,
                                           traffic["max_posts_per_tenant"])
        self.next = {t: 0 for t in ref.TENANTS}
        self.loop = ServingLoop(self.ep, ServingConfig(
            ring_size=traffic["ring_size"],
            max_pending=traffic["outstanding_per_tenant"],
            max_inflight_waves=traffic["max_inflight_waves"],
            mode=cfg["mode"], placement=cfg["placement"]))
        # every post submitted, with its submit time
        self.issued: List[Tuple] = []
        self._unlaunched: List = []
        self.waves: Dict[int, int] = {}     # wave id -> useful bytes
        self._warm_up()

    # -- the client ---------------------------------------------------------

    def _submit(self, tenant: str) -> None:
        j = self.next[tenant]
        if j >= len(self.posts[tenant]):
            raise RuntimeError(f"traffic ran out of {tenant} posts: raise "
                               f"max_posts_per_tenant")
        self.next[tenant] = j + 1
        params, home = self.posts[tenant][j]
        with TraceAnnotation("bench.submit"):
            t = time.perf_counter()
            c = self.loop.submit(tenant, self.op[tenant], params, home=home)
        self.issued.append((c, t))
        self._unlaunched.append(c)

    def _poll(self, now_fn) -> List:
        with TraceAnnotation("bench.poll_cq"):
            done = [c for s in self.sessions.values() for c in s.poll_cq()]
        t = now_fn()
        for c in done:
            self._done_at[id(c)] = t
        return done

    def _turn(self) -> List:
        with TraceAnnotation("bench.pump"):
            rep = self.loop.pump()
        if rep.launched:
            wave = [c for c in self._unlaunched
                    if c.wave_handle is not None
                    and c.wave_handle.wave_id == rep.wave_id]
            self._unlaunched = [c for c in self._unlaunched
                                if c.wave_handle is None]
            self.waves[rep.wave_id] = sum(
                work.post_bytes(self.cfg, c.session.tenant, c.params)
                for c in wave)
        done = self._poll(time.perf_counter)
        if not done and not rep.launched and self.ep.in_flight_waves:
            with TraceAnnotation("bench.wait_any"):
                self.ep.wait_any()
            done = self._poll(time.perf_counter)
        return done

    def _fill(self) -> None:
        for _ in range(self.traffic["outstanding_per_tenant"]):
            for t in ref.TENANTS:
                self._submit(t)

    def _drain(self) -> None:
        with TraceAnnotation("bench.drain"):
            self.loop.drain()
        self._poll(time.perf_counter)

    def _warm_up(self) -> None:
        """The window's own wave shape, compiled and run until the
        first ``warmup_waves`` waves retire."""
        self._done_at: Dict[int, float] = {}
        self._fill()
        retired = 0
        while retired < self.traffic["warmup_waves"] * \
                self.traffic["ring_size"]:
            done = self._turn()
            retired += len(done)
            for c in done:
                self._submit(c.session.tenant)
        self._drain()

    def window(self, seconds: float) -> dict:
        """Closed loop for ``seconds``; the window closes at the first
        retirement at or after that time, so it holds whole waves."""
        first = len(self.issued)
        first_wave = max(self.waves, default=-1) + 1
        t0 = time.perf_counter()
        self._fill()
        ok = 0
        while True:
            done = self._turn()
            now = time.perf_counter()
            ok += sum(1 for c in done if c.status == _STATUS_OK)
            if now >= t0 + seconds and (done or not self.ep.in_flight_waves):
                break
            for c in done:
                self._submit(c.session.tenant)
        t1 = now
        self._drain()
        lat = []
        for c, ts in self.issued[first:]:
            if c.done:
                lat.append((self._done_at[id(c)] - ts) * 1e3)
        waves = {w: b for w, b in self.waves.items() if w >= first_wave}
        return {"t0": t0, "t1": t1, "ok": ok, "latencies_ms": lat,
                "wave_bytes": list(waves.values())}

    # -- after the window ---------------------------------------------------

    def release(self) -> None:
        """Bring the final pool to the host and drop the device state."""
        self.ep.host_mem()

    def check(self, control: bool = False) -> Tuple[Dict[str, Tuple], int,
                                                    int]:
        """Every retired post and the pool against the reference."""
        posts, got = [], []
        missing = 0
        for c, _ in self.issued:
            if not c.done:
                missing += 1
                continue
            posts.append(ref.Post(tenant=c.session.tenant, params=c.params,
                                  home=c.home,
                                  order=(c.event.wave, c.seq)))
            got.append((c.ret, c.status))

        def region(tenant, name, row):
            return self.sessions[tenant].read_region(name, device=row)

        counts = ref.compare(self.cfg, self.rows, posts, got, region,
                             control=control)
        failed = missing + sum(1 for _, s in got if s != _STATUS_OK)
        checks = {"posts_missing": (missing, 0),
                  "posts_wrong": (counts["posts_wrong"], 0),
                  "pool_words_wrong": (counts["pool_words_wrong"], 0)}
        return checks, len(self.issued), failed


def build(cfg: dict, traffic: dict, seed: int, n_chips: int) -> Cell:
    return Cell(cfg, traffic, seed, n_chips)


def end_to_end(info: dict) -> Dict[str, float]:
    import stats

    window_s = info["t1"] - info["t0"]
    return {"posts_per_s": stats.rate(info["ok"], window_s),
            "post_p99_ms": stats.percentile(info["latencies_ms"], 99),
            "post_p50_ms": stats.percentile(info["latencies_ms"], 50)}
