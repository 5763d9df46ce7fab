"""Plain reference of the five stock operators, and the data they read.

The data of every memory node (graph, page tables, lock words, KV blocks,
expert slabs) is drawn here from the seed, in the word layout each
operator documents, and written into the system's pool by the driver.
The reference then executes every retired post, one at a time in the
order the system retired them (wave, then arrival), on its own copy of
that data, and says what each post must return and what the reply and
lock words must hold at the end.  It imports nothing of the program.

Semantics (one 8-byte word per entry; offsets in words):

* graph walk ``[start, hops, reply]``: follow ``next`` (word 1 of a
  64-byte node) ``hops`` times, return the key (word 0) of the node
  reached and copy its 8 words to ``reply``.
* three-level page-table walk ``[va, reply]``: ``l2 = pt1[i1]``,
  ``l3 = pt2[l2 + i2]``, ``page = pt3[l3 + i3]``; return ``page`` and
  copy the 512-word page at ``data[page]`` to ``reply``.
* replicated lock ``[latch, state, new, d1, o1, d2, o2]``: take the latch
  (it is free: every latch is released before its post retires), return
  the old state, write ``new`` to the state and to both replicas, release.
* paged KV fetch ``[n, reply]``: for ``i < n``: copy block
  ``blocktable[req[i]]`` to ``reply + i * block``; return ``n``.
* MoE gather ``[k, reply]``: for ``i < k``: copy slab
  ``expert_table[expert_ids[i]]`` to ``reply + i * slab``; return ``k``.

The control breaks one guarantee the configuration states: a post's
asynchronous copies have all landed before its completion.  Under it a
fetch or gather delivers only its first block, and a lock writes no
replica.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

NODE_WORDS = 8
PAGE_WORDS = 512
WORD_BYTES = 8
TENANTS = ("graph", "ptw", "lock", "kv", "moe")


@dataclasses.dataclass
class Row:
    """One memory node's data, as the benchmark drew it."""

    graph: np.ndarray          # (n_nodes, 8)
    position: np.ndarray       # node -> index in the ring order
    order: np.ndarray          # ring order of the nodes
    pt1: np.ndarray
    pt2: np.ndarray
    pt3: np.ndarray
    pages: np.ndarray          # (n_pages * 512,) page data
    vas: np.ndarray            # mapped virtual addresses
    kv_table: np.ndarray       # block id -> word offset in the KV pool
    kv_pool: np.ndarray
    kv_req: np.ndarray         # the block ids every fetch asks for
    moe_table: np.ndarray      # expert id -> word offset of its slab
    moe_weights: np.ndarray
    moe_ids: np.ndarray        # the experts every gather asks for

    def regions(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Region contents by tenant, for the driver to write."""
        return {
            "graph": {"graph": self.graph.reshape(-1)},
            "ptw": {"pt1": self.pt1, "pt2": self.pt2, "pt3": self.pt3,
                    "data": self.pages},
            "kv": {"blocktable": self.kv_table, "kvpool": self.kv_pool,
                   "req": self.kv_req},
            "moe": {"expert_table": self.moe_table,
                    "weights": self.moe_weights,
                    "expert_ids": self.moe_ids},
        }


def block_words(cfg: dict) -> int:
    return cfg["kv"]["block_bytes"] // WORD_BYTES


def slab_words(cfg: dict) -> int:
    return cfg["moe"]["slab_bytes"] // WORD_BYTES


def make_row(cfg: dict, seed: int, row: int) -> Row:
    """Draw one memory node's data from ``(seed, row)``."""
    rng = np.random.default_rng([seed, row, 0x7A7A])
    n = cfg["graph"]["n_nodes"]
    order = rng.permutation(n)
    position = np.empty(n, np.int64)
    position[order] = np.arange(n)
    graph = np.empty((n, NODE_WORDS), np.int64)
    graph[:, 0] = rng.integers(1, 1 << 62, size=n)
    graph[order, 1] = np.roll(order, -1) * NODE_WORDS
    graph[:, 2:] = rng.integers(0, 1 << 62, size=(n, NODE_WORDS - 2))

    p = cfg["ptw"]
    f, n_pages = p["fanout"], p["n_pages"]
    bits = f.bit_length() - 1
    shift = PAGE_WORDS.bit_length() - 1
    n_tables = p["l3_tables"]
    per_table = n_pages // n_tables
    pairs = rng.choice(f * f, size=n_tables, replace=False)
    pt1 = np.zeros(f, np.int64)
    pt2 = np.zeros(f * f, np.int64)
    pt3 = np.zeros(p["pt3_words"], np.int64)
    phys = rng.permutation(n_pages) * PAGE_WORDS
    l2_of: Dict[int, int] = {}
    vas = []
    k = 0
    for t, pair in enumerate(pairs):
        i1, i2 = int(pair) // f, int(pair) % f
        if i1 not in l2_of:
            l2_of[i1] = len(l2_of) * f
            pt1[i1] = l2_of[i1]
        pt2[l2_of[i1] + i2] = t * f
        for i3 in rng.choice(f, size=per_table, replace=False):
            pt3[t * f + int(i3)] = phys[k]
            k += 1
            vas.append((i1 << (shift + 2 * bits)) | (i2 << (shift + bits))
                       | (int(i3) << shift))
    pages = rng.integers(0, 1 << 62, size=n_pages * PAGE_WORDS)

    kvc, bw = cfg["kv"], block_words(cfg)
    kv_table = rng.permutation(kvc["n_blocks"]).astype(np.int64) * bw
    kv_pool = rng.integers(0, 1 << 62, size=kvc["n_blocks"] * bw)
    kv_req = rng.choice(kvc["n_blocks"], size=kvc["max_req_blocks"],
                        replace=False).astype(np.int64)
    mc, sw = cfg["moe"], slab_words(cfg)
    moe_table = rng.permutation(mc["n_experts"]).astype(np.int64) * sw
    moe_weights = rng.integers(0, 1 << 62, size=mc["n_experts"] * sw)
    moe_ids = rng.choice(mc["n_experts"], size=mc["max_k"],
                         replace=False).astype(np.int64)
    return Row(graph=graph, position=position, order=order, pt1=pt1,
               pt2=pt2, pt3=pt3, pages=pages, vas=np.asarray(vas, np.int64),
               kv_table=kv_table, kv_pool=kv_pool, kv_req=kv_req,
               moe_table=moe_table, moe_weights=moe_weights,
               moe_ids=moe_ids)


def translate(cfg: dict, row: Row, va: int) -> int:
    f = cfg["ptw"]["fanout"]
    bits = f.bit_length() - 1
    shift = PAGE_WORDS.bit_length() - 1
    i1 = (va >> (shift + 2 * bits)) & (f - 1)
    i2 = (va >> (shift + bits)) & (f - 1)
    i3 = (va >> shift) & (f - 1)
    return int(row.pt3[row.pt2[row.pt1[i1] + i2] + i3])


@dataclasses.dataclass
class Post:
    """One retired post as the reference sees it."""

    tenant: str
    params: Tuple[int, ...]
    home: int
    order: Tuple[int, int]     # (wave, arrival) of its retirement


@dataclasses.dataclass
class Expected:
    """What the reference says: per post (ret, status), and the final
    words of every region a post writes, by (tenant, region, row)."""

    results: List[Tuple[int, int]]
    regions: Dict[Tuple[str, str, int], np.ndarray]


def reply_words(cfg: dict, tenant: str) -> int:
    """Words of one reply slot."""
    return {"graph": NODE_WORDS, "ptw": PAGE_WORDS,
            "kv": cfg["kv"]["max_req_blocks"] * block_words(cfg),
            "moe": cfg["moe"]["max_k"] * slab_words(cfg)}[tenant]


def execute(cfg: dict, rows: Sequence[Row], posts: Sequence[Post], *,
            control: bool = False) -> Expected:
    """Run ``posts`` one at a time in their retirement order."""
    status_ok = 0
    n_dev = len(rows)
    slots = cfg["reply_slots"]
    reply = {(t, "reply", d): np.zeros(slots * reply_words(cfg, t),
                                       np.int64)
             for t in ("graph", "ptw", "kv", "moe") for d in range(n_dev)}
    lock = {("lock", "lock", d): np.zeros(cfg["lock"]["words"], np.int64)
            for d in range(n_dev)}
    bw, sw = block_words(cfg), slab_words(cfg)
    results: List[Tuple[int, int]] = [(0, 0)] * len(posts)
    for i in sorted(range(len(posts)), key=lambda i: posts[i].order):
        p = posts[i]
        r = rows[p.home]
        a = p.params
        if p.tenant == "graph":
            node = int(r.order[(r.position[a[0] // NODE_WORDS] + a[1])
                               % len(r.order)])
            reply[("graph", "reply", p.home)][a[2]:a[2] + NODE_WORDS] = \
                r.graph[node]
            results[i] = (int(r.graph[node, 0]), status_ok)
        elif p.tenant == "ptw":
            page = translate(cfg, r, a[0])
            reply[("ptw", "reply", p.home)][a[1]:a[1] + PAGE_WORDS] = \
                r.pages[page:page + PAGE_WORDS]
            results[i] = (page, status_ok)
        elif p.tenant == "lock":
            words = lock[("lock", "lock", p.home)]
            old = int(words[a[1]])
            words[a[1]] = a[2]
            if not control:
                lock[("lock", "lock", a[3])][a[4]] = a[2]
                lock[("lock", "lock", a[5])][a[6]] = a[2]
            results[i] = (old, status_ok)
        elif p.tenant in ("kv", "moe"):
            table, data, ids, w = (
                (r.kv_table, r.kv_pool, r.kv_req, bw) if p.tenant == "kv"
                else (r.moe_table, r.moe_weights, r.moe_ids, sw))
            dst = reply[(p.tenant, "reply", p.home)]
            for j in range(1 if control else a[0]):
                src = int(table[ids[j]])
                dst[a[1] + j * w:a[1] + (j + 1) * w] = data[src:src + w]
            results[i] = (a[0], status_ok)
        else:
            raise ValueError(f"unknown tenant {p.tenant!r}")
    return Expected(results=results, regions={**reply, **lock})


def compare(cfg: dict, rows: Sequence[Row], posts: Sequence[Post],
            got_results: Sequence[Tuple[int, int]],
            got_region, *, control: bool = False) -> Dict[str, int]:
    """Counts of what differs from the reference.

    ``got_region(tenant, region, row)`` returns the system's final words
    of one region; the data regions must hold what was written at set-up,
    the reply and lock regions what :func:`execute` says."""
    exp = execute(cfg, rows, posts, control=control)
    wrong_posts = sum(1 for e, g in zip(exp.results, got_results)
                      if tuple(e) != tuple(g))
    wrong_words = 0
    for (tenant, region, d), words in exp.regions.items():
        got = np.asarray(got_region(tenant, region, d))[:words.size]
        wrong_words += int(np.count_nonzero(got != words))
    for d, row in enumerate(rows):
        for tenant, regs in row.regions().items():
            for region, words in regs.items():
                got = np.asarray(got_region(tenant, region, d))
                wrong_words += int(np.count_nonzero(
                    got[:words.size] != words))
    return {"posts_wrong": wrong_posts, "pool_words_wrong": wrong_words}
