"""Plain float32 reference of the dense GQA decoder, and its fp8 control.

It follows the decoder block as the program computes it (each departure
from the published models is listed in the configuration files):

    x = embed[tokens]
    per layer:
        h = rmsnorm(x) * g_attn
        q, k, v = h Wq, h Wk, h Wv;  rotary (two halves) on q and k
        x = x + causal_softmax(q k^T / sqrt(D)) v  Wo     (GQA: head i
                                                  reads kv head i // G)
        h = rmsnorm(x) * g_ffn
        x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * g_f) Whead

It imports nothing of the program.  The weights come from
:mod:`bench.lib.weights` and are upcast to float32 one layer at a time;
every product runs at ``Precision.HIGHEST``, attention in blocks of
queries, the head only at the rows asked for.

``precision="fp8"`` is the control: the same forward with both operands
of every projection and of the head rounded to float8_e4m3fn, weights
with one scale per output column and activations with one per row,
products accumulated in float32.  It is the lower precision that would
tempt a later change to the bfloat16 configurations.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # queries per attention block
PAD_TO = 512           # sequences are padded to a multiple of this
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _round_fp8(x, axis):
    """Round to float8_e4m3fn with one absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(x, w, precision):
    """x (T, k) @ w (k, n) in float32, or with fp8 operands."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _round_fp8(x, axis=-1)
        w = _round_fp8(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _rope(x, theta):
    """Rotary over the whole head, as two halves; x (T, H, D)."""
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal attention in query blocks; q (T, H, D), k/v (T, Hkv, D)."""
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
    return out.reshape(T, H * D)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, w, i, *, spec, precision):
    at = lambda name: w[name][i]  # noqa: E731
    T = x.shape[0]
    h = _rms(x, at("norm_attn"), spec.norm_eps)
    q = _mm(h, at("wq"), precision).reshape(T, spec.n_heads, spec.head_dim)
    k = _mm(h, at("wk"), precision).reshape(T, spec.n_kv_heads,
                                            spec.head_dim)
    v = _mm(h, at("wv"), precision).reshape(T, spec.n_kv_heads,
                                            spec.head_dim)
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    x = x + _mm(_attention(q, k, v), at("wo"), precision)
    h = _rms(x, at("norm_ffn"), spec.norm_eps)
    g = _mm(h, at("w_gate"), precision)
    u = _mm(h, at("w_up"), precision)
    return x + _mm(jax.nn.silu(g) * u, at("w_down"), precision)


@functools.partial(jax.jit, static_argnames=("spec",))
def _embed(w, tokens, *, spec):
    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _head(x, w, rows, *, spec, precision):
    h = _rms(x[rows], w["norm_f"], spec.norm_eps)
    return _mm(h, w["head"], precision)


def logits_at(w: dict, spec, tokens: Sequence[int], rows: Sequence[int],
              precision: str = "fp32") -> np.ndarray:
    """Logits ``(len(rows), vocab)`` of the sequence ``tokens`` at the
    positions ``rows`` (row ``r`` predicts token ``r + 1``)."""
    if precision not in ("fp32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    n = len(tokens)
    padded = -(-n // PAD_TO) * PAD_TO
    toks = np.zeros(padded, np.int32)
    toks[:n] = tokens
    x = _embed(w, jnp.asarray(toks), spec=spec)
    for i in range(spec.n_layers):
        x = _layer(x, w, jnp.int32(i), spec=spec, precision=precision)
    out = _head(x, w, jnp.asarray(np.asarray(rows, np.int32)), spec=spec,
                precision=precision)
    return np.asarray(out)


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
