"""Math that every block's reference shares, and the check's gap.

A block (``bench/reference/<block>.py``) builds its float32 forward and
its fp8 control from these pieces, so that two blocks round, multiply
and attend alike:

- :func:`seed_key`: the PRNG key of a seed of any size;
- :func:`round_fp8`, :func:`mm`: a product in float32 at ``HIGHEST``
  precision, or with both operands rounded to float8_e4m3fn (the
  control);
- :func:`rms`: RMS norm with a gain;
- :func:`attention`: causal softmax attention in blocks of queries;
- :func:`served_rows`, :func:`widest_gap`: what the check compares;
- :func:`dtype_bytes`: bytes of a served dtype, for the counts.

It imports nothing of the program.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
PAD_TO = 512           # sequences are padded to a multiple of this
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed of any size (a plain
    ``jax.random.key`` drops the bits above 32 without 64-bit mode)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def round_fp8(x, axis):
    """Round to float8_e4m3fn with one absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def mm(x, w, precision):
    """x (T, k) @ w (k, n) in float32, or with fp8 operands: weights
    with one scale per output column, activations one per row."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = round_fp8(x, axis=-1)
        w = round_fp8(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def rms(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def attention(q, k, v):
    """Causal attention in query blocks; q (T, H, D), k/v (T, Hkv, D):
    query head i reads key/value head ``i // (H // Hkv)``."""
    T, H, D = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    qb = q.reshape(T // Q_BLOCK, Q_BLOCK, H, D)
    cols = jnp.arange(T)

    def block(args):
        i, qi = args
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / np.sqrt(D)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(T // Q_BLOCK), qb))
    return out.reshape(T, -1)


def served_rows(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits choose the served tokens 0 .. n-1."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def widest_gap(ref: np.ndarray, tokens: Sequence[int]) -> float:
    """How far below the reference's best logit a chosen token lies, at
    its worst over the rows (0 where every token is the reference's
    first choice)."""
    ref = np.asarray(ref, np.float64)
    chosen = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max(ref.max(axis=-1) - chosen))


def dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]
