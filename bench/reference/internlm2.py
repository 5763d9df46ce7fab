"""InternLM2 as published, in plain ``jax.numpy`` and float32.

The weights are the benchmark's own: :func:`init_params` draws them from
the seed on the device, in one jitted call and in the type they are
served in, laid out as the serving program takes them.  :func:`logit_gaps`
runs the whole causal forward pass over each prompt with its served
tokens, at ``highest`` matmul precision and with no cache, kernel or
batching trick, and says by how much each served token's logit lies below
the best logit at its position.  It imports nothing of the program.

Architecture (arXiv:2403.17297; ``internlm/internlm2-1_8b`` config.json):
pre-norm decoder, RMSNorm (eps ``rms_norm_eps``), grouped-query attention
with rotary position embedding (base ``rope_theta``, the two halves of
each head rotated together), query head ``h`` reading key/value head
``h // (heads / kv_heads)``, SwiGLU feed-forward, untied output head, no
biases.

The control (:func:`logit_gaps` with ``fp8=True``) is the same forward
pass with every matrix product taken on float8 (e4m3) operands, each
row of activations and each column of weights scaled into its range: the
next precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def sizes(cfg: dict) -> Dict[str, int]:
    m = cfg["model"]
    return {"layers": m["num_hidden_layers"], "d": m["hidden_size"],
            "heads": m["num_attention_heads"],
            "kv_heads": m["num_key_value_heads"],
            "head_dim": m["hidden_size"] // m["num_attention_heads"],
            "ffn": m["intermediate_size"], "vocab": m["vocab_size"],
            "vocab_rows": (m["vocab_size"] + 255) // 256 * 256}


def key_of(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def init_params(cfg: dict, seed: int):
    """Random weights, N(0, 1/fan_in) for matrices and N(0, 1) for the
    token embedding, in bfloat16, in the serving program's tree layout;
    the embedding and the head have their rows padded to a multiple of
    256 and the padding is never read."""
    s = sizes(cfg)
    L, D, F = s["layers"], s["d"], s["ffn"]
    H, KV, hd, Vp = s["heads"], s["kv_heads"], s["head_dim"], \
        s["vocab_rows"]
    dtype = jnp.dtype(cfg["model"]["torch_dtype"])

    def draw(key):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, fan):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * fan ** -0.5).astype(dtype)

        ones = jnp.ones
        return {
            "embed": {"tokens": mat((Vp, D), 1), "head": mat((D, Vp), D)},
            "final_norm": {"scale": ones((D,), dtype)},
            "blocks": ({
                "norm1": {"scale": ones((L, D), dtype)},
                "mix": {"wq": mat((L, D, H * hd), D),
                        "wk": mat((L, D, KV * hd), D),
                        "wv": mat((L, D, KV * hd), D),
                        "wo": mat((L, H * hd, D), H * hd)},
                "norm2": {"scale": ones((L, D), dtype)},
                "ffn": {"wi": mat((L, D, F), D), "wg": mat((L, D, F), D),
                        "wo": mat((L, F, D), F)},
            },),
        }

    return jax.jit(draw)(key_of(seed))


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along
    ``axis``, and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _matmul(x, w, fp8):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return x @ w


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, heads, hd); positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward_gaps(params, tokens, served, cfg_static, fp8):
    """Gaps (B, S): best logit minus the logit of ``served[b, s]``, the
    token served after position s; and the gap, under the float32
    logits, of the token this pass puts first."""
    eps, theta, H, KV, hd, V = cfg_static
    B, S = tokens.shape
    x = params["embed"]["tokens"][tokens].astype(jnp.float32)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _rms(x, p["norm1"]["scale"], eps)
        q = _matmul(h, p["mix"]["wq"], fp8).reshape(B, S, H, hd)
        k = _matmul(h, p["mix"]["wk"], fp8).reshape(B, S, KV, hd)
        v = _matmul(h, p["mix"]["wv"], fp8).reshape(B, S, KV, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        g = H // KV
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        s = jnp.where(mask[None, None], s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        x = x + _matmul(a.reshape(B, S, H * hd), p["mix"]["wo"], fp8)
        h = _rms(x, p["norm2"]["scale"], eps)
        f = jax.nn.silu(_matmul(h, p["ffn"]["wg"], fp8)) \
            * _matmul(h, p["ffn"]["wi"], fp8)
        return x + _matmul(f, p["ffn"]["wo"], fp8), None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = _matmul(x, params["embed"]["head"][:, :V], fp8)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return best - got, jnp.argmax(logits, axis=-1)


_forward = jax.jit(_forward_gaps, static_argnums=(3, 4))


def logit_gaps(cfg: dict, params, sequences: Sequence[Tuple[List[int],
                                                            List[int]]],
               length: int, *, fp8: bool = False) -> List[np.ndarray]:
    """For each ``(prompt, served)`` pair, the gap of every served token
    below the float32 reference's best logit at its position.  With
    ``fp8``, the gap under the float32 reference of the token the float8
    pass puts first.  Sequences are padded to ``length`` and run
    together."""
    s = sizes(cfg)
    m = cfg["model"]
    static = (float(m["rms_norm_eps"]), float(m["rope_theta"]), s["heads"],
              s["kv_heads"], s["head_dim"], s["vocab"])
    B = len(sequences)
    tokens = np.zeros((B, length), np.int32)
    served = np.zeros((B, length), np.int32)
    spans = []
    for b, (prompt, out) in enumerate(sequences):
        full = list(prompt) + list(out)
        if len(full) - 1 > length:
            raise ValueError(f"sequence of {len(full)} tokens exceeds "
                             f"{length}")
        tokens[b, :len(full) - 1] = full[:-1]
        served[b, :len(full) - 1] = full[1:]
        spans.append((len(prompt) - 1, len(full) - 1))
    with jax.default_matmul_precision("highest"):
        gap, top = _forward(params, jnp.asarray(tokens), jnp.asarray(served),
                            static, False)
        if fp8:
            _, top8 = _forward(params, jnp.asarray(tokens),
                               jnp.asarray(served), static, True)
            gap, _ = _forward(params, jnp.asarray(tokens), top8, static,
                              False)
    gap = np.asarray(gap)
    return [gap[b, lo:hi] for b, (lo, hi) in enumerate(spans)]
