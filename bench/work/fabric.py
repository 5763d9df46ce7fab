"""Useful bytes of one post, from its operator and parameters alone.

A post's useful bytes are the words its semantics read and write, at
8 bytes a word: the pointers and table entries it follows, the data it
copies (read once, written once) and the lock words it touches.  They
count what the traffic asks for, whatever engine runs it, and so bound
the time below by bytes over the HBM bandwidth.
"""

from __future__ import annotations

from typing import Sequence

WORD = 8
NODE_BYTES = 64
PAGE_BYTES = 4096


def post_bytes(cfg: dict, tenant: str, params: Sequence[int]) -> int:
    if tenant == "graph":
        # one next pointer per hop, the key, the node read and written
        return params[1] * WORD + WORD + 2 * NODE_BYTES
    if tenant == "ptw":
        # three table entries, the page read and written
        return 3 * WORD + 2 * PAGE_BYTES
    if tenant == "lock":
        # latch compare-and-swap (read, write) and release, the state
        # read and written, and each of two replicas read and written
        return 9 * WORD
    if tenant == "kv":
        # per block: its id, its table entry, the block read and written
        return params[0] * (2 * WORD + 2 * cfg["kv"]["block_bytes"])
    if tenant == "moe":
        return params[0] * (2 * WORD + 2 * cfg["moe"]["slab_bytes"])
    raise ValueError(f"unknown tenant {tenant!r}")
