"""Work of a decode step, from the model's shapes and the sequence
lengths alone: the bytes the step must read and the model operations it
must do, whatever engine runs it."""

from __future__ import annotations

from typing import Sequence

from reference.internlm2 import sizes

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _elem(cfg: dict) -> int:
    return BYTES[cfg["model"]["torch_dtype"]]


def layer_matmul_params(cfg: dict) -> int:
    s = sizes(cfg)
    d, hd = s["d"], s["head_dim"]
    return (d * s["heads"] * hd + 2 * d * s["kv_heads"] * hd
            + s["heads"] * hd * d + 3 * d * s["ffn"])


def weight_bytes(cfg: dict, embed_rows: int) -> int:
    """Every weight a step reads: each layer's matrices and norms, the
    final norm, the output head over the vocabulary and the embedding
    rows of the step's input tokens."""
    s = sizes(cfg)
    d = s["d"]
    elems = s["layers"] * (layer_matmul_params(cfg) + 2 * d) + d \
        + d * s["vocab"] + embed_rows * d
    return elems * _elem(cfg)


def kv_bytes(cfg: dict, attended: Sequence[int]) -> int:
    """Keys and values read by the decode of each sequence, up to its
    length (``attended`` tokens each, the new one included)."""
    s = sizes(cfg)
    per_token = 2 * s["kv_heads"] * s["head_dim"] * _elem(cfg) \
        * s["layers"]
    return sum(attended) * per_token


def decode_bytes(cfg: dict, attended: Sequence[int]) -> int:
    return weight_bytes(cfg, len(attended)) + kv_bytes(cfg, attended)


def step_flops(cfg: dict, prefills: Sequence[int],
               attended: Sequence[int]) -> int:
    """Model operations of one engine step: a decode for each sequence
    in ``attended`` and a prefill of each prompt length in ``prefills``
    (its causal attention and the logits of its last position)."""
    s = sizes(cfg)
    lm = s["layers"] * layer_matmul_params(cfg)
    head = s["d"] * s["vocab"]
    att = 4 * s["layers"] * s["heads"] * s["head_dim"]
    flops = sum(2 * (lm + head) + att * a for a in attended)
    flops += sum(2 * lm * p + 2 * head + att * p * (p + 1) // 2
                 for p in prefills)
    return flops
