"""A served model through ``ServingEngine(resolver="tiara")``.

The engine holds a fixed number of slots; the client keeps every slot
busy (a closed loop: when a sequence ends, the next request is submitted
before the next step) and steps the engine.  Each step resolves every
active sequence's block table through the fabric (one VM wave on the
endpoint), prefills admitted prompts and decodes one token for every
slot.

Host spans: ``bench.engine_step`` around each ``ServingEngine.step`` and
``bench.submit`` around each submission.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

import generate
from reference import internlm2 as ref
from work import decode as work


def program_config(cfg: dict):
    """The serving program's architecture record, sized from the file."""
    from repro.configs import get_config

    s = ref.sizes(cfg)
    m = cfg["model"]
    return dataclasses.replace(
        get_config(cfg["program_config"]), n_layers=s["layers"],
        d_model=s["d"], n_heads=s["heads"], n_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ffn"], vocab=s["vocab"],
        rope_theta=float(m["rope_theta"]),
        page_size=cfg["serving"]["page_tokens"],
        dtype=m["torch_dtype"], param_dtype=m["torch_dtype"])


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 n_chips: int) -> None:
        import jax

        from repro.serving import ServingEngine

        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        srv = cfg["serving"]
        self.params = ref.init_params(cfg, seed)
        jax.block_until_ready(self.params)
        self.engine = ServingEngine(
            program_config(cfg), self.params, max_slots=traffic["slots"],
            max_seq=srv["max_seq"], eos_id=-1, resolver=srv["resolver"],
            n_homes=srv["memory_nodes"])
        self.requests = generate.decode_requests(
            traffic, ref.sizes(cfg)["vocab"], seed, traffic["max_requests"])
        self.next = 0
        self.live: Dict[int, Tuple] = {}     # sid -> (handle, request)
        self.seen: Dict[int, int] = {}       # sid -> tokens observed
        self.token_t: Dict[int, float] = {}  # sid -> last token's time
        self.finished: List[Tuple] = []      # (request, tokens)
        self.failed = 0
        self._warm_up()

    def _submit(self) -> None:
        if self.next >= len(self.requests):
            raise RuntimeError("traffic ran out of requests: raise "
                               "max_requests")
        req = self.requests[self.next]
        self.next += 1
        with TraceAnnotation("bench.submit"):
            h = self.engine.submit(req.prompt.tolist(), max_new=req.max_new)
        self.live[h.sid] = (h, req)
        self.seen[h.sid] = 0

    def _step(self, record) -> None:
        """One engine step; ``record(sid, k, t)`` sees every new token
        (its 1-based index ``k``), then finished slots are refilled."""
        with TraceAnnotation("bench.engine_step"):
            self.engine.step()
        t = time.perf_counter()
        ended = []
        for sid, (h, req) in self.live.items():
            toks = h.tokens
            for k in range(self.seen[sid] + 1, len(toks) + 1):
                record(req, k, t, self.token_t.get(sid))
                self.token_t[sid] = t
            self.seen[sid] = len(toks)
            if h.done:
                ended.append(sid)
        for sid in ended:
            h, req = self.live.pop(sid)
            if h.ok:
                self.finished.append((req, h.tokens))
            else:
                self.failed += 1
            self._submit()

    def _warm_up(self) -> None:
        """Compile one prefill per prompt bucket of the mix, then fill
        every slot with the mix's first requests and take one step."""
        lo, hi = self.traffic["prompt_tokens"]
        page = self.cfg["serving"]["page_tokens"]
        bucket = page
        vocab = ref.sizes(self.cfg)["vocab"]
        rng = np.random.default_rng([self.seed, 0x3A3])
        while bucket < 2 * hi:
            if bucket >= lo:
                h = self.engine.submit(
                    rng.integers(0, vocab, size=bucket).tolist(), max_new=2)
                self.engine.run_until(h.sid)
            bucket *= 2
        for _ in range(self.traffic["slots"]):
            self._submit()
        self._step(lambda *a: None)

    def window(self, seconds: float) -> dict:
        # per step: prompt lengths prefilled, tokens attended by each
        # decode
        steps: List[Tuple[List[int], List[int]]] = []
        gaps_ms: List[float] = []
        tokens = [0]

        def record(req, k, t, prev):
            tokens[0] += 1
            if prev is not None:
                gaps_ms.append((t - prev) * 1e3)
            if k == 1:
                steps[-1][0].append(len(req.prompt))
            else:
                steps[-1][1].append(len(req.prompt) + k - 1)

        t0 = time.perf_counter()
        while True:
            steps.append(([], []))
            self._step(record)
            now = time.perf_counter()
            if now >= t0 + seconds:
                break
        return {"t0": t0, "t1": now, "tokens": tokens[0],
                "tpot_ms": gaps_ms, "steps": steps,
                "flops": sum(work.step_flops(self.cfg, p, d)
                             for p, d in steps),
                "decode_bytes": [work.decode_bytes(self.cfg, d)
                                 for _, d in steps if d],
                "kv_bytes": [work.kv_bytes(self.cfg, d)
                             for _, d in steps if d]}

    def release(self) -> None:
        """Drop the engine (its caches and its endpoint); the weights are
        the benchmark's and stay for the reference."""
        self.engine = None

    def check(self, fp8: bool = False) -> Tuple[Dict[str, Tuple], int,
                                                 int]:
        """A sample of finished sequences, drawn from the seed with the
        longest among them, against the float32 reference."""
        n = self.traffic["check_sequences"]
        done = self.finished
        if not done:
            return {"sequences_checked": (0, -1)}, 0, self.failed
        order = sorted(range(len(done)),
                       key=lambda i: -(len(done[i][0].prompt)
                                       + len(done[i][1])))
        rng = np.random.default_rng([self.seed, 0xC4EC])
        rest = order[1:]
        pick = [order[0]] + [rest[i] for i in rng.choice(
            len(rest), size=min(n - 1, len(rest)), replace=False)]
        seqs = [(done[i][0].prompt.tolist(), done[i][1]) for i in pick]
        gaps = ref.logit_gaps(self.cfg, self.params, seqs,
                              self.cfg["serving"]["max_seq"], fp8=fp8)
        widest = float(max(g.max() for g in gaps))
        limit = self.cfg["check"]["served_logit_gap_limit"]
        checks = {"served_logit_gap_max": (widest, limit),
                  "sequences_failed": (self.failed, 0)}
        attempted = self.next
        return checks, attempted, self.failed


def build(cfg: dict, traffic: dict, seed: int, n_chips: int) -> Cell:
    return Cell(cfg, traffic, seed, n_chips)


def end_to_end(info: dict) -> Dict[str, float]:
    import stats

    return {"decode_tokens_per_s": stats.rate(info["tokens"],
                                              info["t1"] - info["t0"]),
            "tpot_p99_ms": stats.percentile(info["tpot_ms"], 99),
            "tpot_p95_ms": stats.percentile(info["tpot_ms"], 95),
            "tpot_p50_ms": stats.percentile(info["tpot_ms"], 50)}
