"""The one traffic generator: every mix is a data file under
``bench/traffic/`` that these functions read.

Work is drawn from the seed alone, so the same seed gives the same
posts and requests, and the system receives only the generated inputs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference.fabric import NODE_WORDS, PAGE_WORDS, Row, reply_words


def fabric_posts(cfg: dict, traffic: dict, rows: Sequence[Row], seed: int,
                 n_posts: int) -> Dict[str, List[Tuple[Tuple[int, ...],
                                                       int]]]:
    """``n_posts`` posts per tenant, as ``(params, home)``.

    Post ``j`` of a tenant is homed on row ``j mod rows`` and writes
    reply slot ``j mod reply_slots``; lock post ``j`` takes pair
    ``j mod pairs`` with its replicas on the next two rows."""
    rng = np.random.default_rng([seed, 0xB0B])
    n_dev = len(rows)
    slots = cfg["reply_slots"]
    j = np.arange(n_posts)
    home = j % n_dev
    slot = j % slots
    n_nodes = cfg["graph"]["n_nodes"]
    lo, hi = traffic["graph_hops"]
    starts = rng.integers(0, n_nodes, size=n_posts) * NODE_WORDS
    hops = rng.integers(lo, hi + 1, size=n_posts)
    va_pick = rng.integers(0, 1 << 62, size=n_posts)
    klo, khi = traffic["kv_blocks"]
    kv_n = rng.integers(klo, khi + 1, size=n_posts)
    mlo, mhi = traffic["moe_experts"]
    moe_k = rng.integers(mlo, mhi + 1, size=n_posts)
    new_vals = rng.integers(1, 1 << 62, size=n_posts)
    pairs = cfg["lock"]["pairs"]
    out: Dict[str, List[Tuple[Tuple[int, ...], int]]] = {
        "graph": [], "ptw": [], "lock": [], "kv": [], "moe": []}
    for i in range(n_posts):
        h = int(home[i])
        r = rows[h]
        s = int(slot[i])
        p = i % pairs
        out["graph"].append(((int(starts[i]), int(hops[i]),
                              s * NODE_WORDS), h))
        out["ptw"].append(((int(r.vas[va_pick[i] % len(r.vas)]),
                            s * PAGE_WORDS), h))
        out["lock"].append(((2 * p, 2 * p + 1, int(new_vals[i]),
                             (h + 1) % n_dev, 2 * p + 1, (h + 2) % n_dev,
                             2 * p + 1), h))
        out["kv"].append(((int(kv_n[i]), s * reply_words(cfg, "kv")), h))
        out["moe"].append(((int(moe_k[i]), s * reply_words(cfg, "moe")), h))
    return out


@dataclasses.dataclass
class Request:
    prompt: np.ndarray         # token ids; one array, not a list of ints,
    max_new: int               # so that a run holds few Python objects


def log_uniform(rng: np.random.Generator, lo: int, hi: int,
                size: int) -> np.ndarray:
    return np.floor(np.exp(rng.uniform(math.log(lo), math.log(hi + 1),
                                       size=size))).astype(np.int64)


def decode_requests(traffic: dict, vocab: int, seed: int,
                    n: int) -> List[Request]:
    """``n`` requests: prompt lengths log-uniform, output lengths uniform,
    token ids uniform over the vocabulary.  The first ``slots`` requests
    fill the engine at the start, and each keeps only a remainder of its
    output, drawn from 1 to its length, so that the sequences end at
    spread-out times from the first step on."""
    rng = np.random.default_rng([seed, 0xDEC])
    plo, phi = traffic["prompt_tokens"]
    olo, ohi = traffic["output_tokens"]
    plen = log_uniform(rng, plo, phi, n)
    olen = rng.integers(olo, ohi + 1, size=n)
    slots = traffic["slots"]
    olen[:slots] = rng.integers(1, olen[:slots] + 1)
    return [Request(prompt=rng.integers(0, vocab, size=int(p)),
                    max_new=int(o)) for p, o in zip(plen, olen)]
