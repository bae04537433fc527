"""The dense GQA decoder block: its spec, weights, float32 reference,
fp8 control and counts.

A configuration file names its block (``"block": "dense"``), and the
harness loads ``bench/reference/<block>.py`` by path.  Every block
provides the same functions; what they share is in
:mod:`bench.reference.common`:

- :func:`load_spec` (the sizes; ``vocab`` and ``dtype`` are read by the
  harness) and :func:`program_config` (the program's config, checked
  against the file);
- :func:`plain_weights` and :func:`program_weights`: the same values
  from the same seed, in the reference's layout and in the program's;
- :func:`logits_at`: the reference, ``"fp32"``, or the ``"fp8"``
  control;
- :func:`prefill_flops`, :func:`decode_flops`, :func:`prefill_bytes`,
  :func:`decode_bytes`, :func:`weight_bytes`: what serving needs.

**The file.**  ``bench/configs/<config>.json`` holds the published
config under the published key names.  Where the program's block
computes something else than the published model (a norm, a rotary
share, a bias), the file lists it under ``departures``; every other key
is run as published.  ``arch`` names the program's model in its config
registry and ``changes`` the fields of the program's config that the
file sets on top of it (the served dtype, the published rotary base, or
everything for a test's tiny model).

**The weights** are the benchmark's, made on the device from the seed in
one jitted call.  The reference makes its own copy from the seed after
the program is gone, so it takes nothing that the program made.  The
init law: the embedding has unit variance, every projection
``1/sqrt(fan_in)``, so that each layer's attention and MLP add a term of
the same order as the stream and the logits move with every layer and
with the cache.  ``q`` and ``k`` are scaled so that attention scores
have a standard deviation of 2, which makes attention prefer some
positions over others instead of averaging them all.  Norm gains are
``1 + 0.1 N(0, 1)``.

**The reference** follows the block as the program computes it (each
departure from the published models is listed in the configuration
files)::

    x = embed[tokens]
    per layer:
        h = rmsnorm(x) * g_attn
        q, k, v = h Wq, h Wk, h Wv;  rotary (two halves) on q and k
        x = x + causal_softmax(q k^T / sqrt(D)) v  Wo     (GQA: head i
                                                  reads kv head i // G)
        h = rmsnorm(x) * g_ffn
        x = x + (silu(h Wgate) * (h Wup)) Wdown
    logits = (rmsnorm(x) * g_f) Whead

It imports nothing of the program.  The weights are upcast to float32
one layer at a time; every product runs at ``Precision.HIGHEST``,
attention in blocks of queries, the head only at the rows asked for.
``precision="fp8"`` is the control: the same forward with both operands
of every projection and of the head rounded to float8_e4m3fn, products
accumulated in float32.  It is the lower precision that would tempt a
later change to the bfloat16 configurations.

**The counts** are of the work the request needs, not of what a program
happens to do:

- FLOPs: the projections (q, k, v, o, gate, up, down: 2 per
  multiply-add), causal attention at the actual context (scores and
  values: ``4 * heads * head_dim`` per query-key pair), and the LM head
  at the one position whose token is sampled.  A program that computes
  logits at every prompt position, or attends over a whole slab, spends
  more time for the same count.
- Bytes: every layer weight and the head read once per prefill and once
  per decode step, the embedding rows of the tokens fed, K and V written
  for each new token and, in a decode step, read at the actual context
  (not the slab's length): ``context - 1`` tokens read plus the new one
  written.  Activations are left out.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import (PAD_TO, attention, dtype_bytes, mm, rms,
                                    seed_key)


# ---------------------------------------------------------------------------
# The spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Spec:
    """The dense GQA decoder a configuration file describes."""
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str                 # the served dtype of weights and compute
    raw: dict = dataclasses.field(default_factory=dict, compare=False,
                                  repr=False)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def load_spec(raw: dict, name: str) -> Spec:
    heads = int(raw["num_attention_heads"])
    d = int(raw["hidden_size"])
    return Spec(
        name=name,
        n_layers=int(raw["num_hidden_layers"]), d_model=d, n_heads=heads,
        n_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw.get("head_dim") or d // heads),
        d_ff=int(raw["intermediate_size"]), vocab=int(raw["vocab_size"]),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw.get("rms_norm_eps", raw.get("layer_norm_eps"))),
        dtype=str(raw["torch_dtype"]),
        raw=raw)


def program_config(spec: Spec):
    """The program's ``ModelConfig`` for this file: its registered arch
    with ``changes`` applied, checked field by field against the file."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(spec.raw["arch"]),
                              **spec.raw.get("changes", {}))
    want = {
        "n_layers": spec.n_layers, "d_model": spec.d_model,
        "n_heads": spec.n_heads, "n_kv_heads": spec.n_kv_heads,
        "resolved_head_dim": spec.head_dim, "d_ff": spec.d_ff,
        "vocab_size": spec.vocab, "rope_theta": spec.rope_theta,
        "norm_eps": spec.norm_eps, "param_dtype": spec.dtype,
        "compute_dtype": spec.dtype, "family": "dense", "attn_kind": "gqa",
        "act": "silu", "frontend": "tokens", "n_codebooks": 1,
        "tie_embeddings": False, "rope_kind": "rope",
        "sliding_window": spec.raw.get("sliding_window"),
    }
    bad = {k: (getattr(cfg, k), v) for k, v in want.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"config {spec.name}: the program's config differs "
                         f"from the file (program, file): {bad}")
    return cfg


# ---------------------------------------------------------------------------
# The weights
# ---------------------------------------------------------------------------

# Attention scores q.k/sqrt(head_dim) get this variance (see above).
SCORE_VAR = 4.0


def _shapes(spec: Spec) -> dict:
    L, d, f, V = spec.n_layers, spec.d_model, spec.d_ff, spec.vocab
    return {
        "embed": (V, d), "norm_attn": (L, d), "wq": (L, d, spec.q_dim),
        "wk": (L, d, spec.kv_dim), "wv": (L, d, spec.kv_dim),
        "wo": (L, spec.q_dim, d), "norm_ffn": (L, d), "w_gate": (L, d, f),
        "w_up": (L, d, f), "w_down": (L, f, d), "norm_f": (d,),
        "head": (d, V),
    }


def _std(name: str, shape) -> float:
    if name == "embed":
        return 1.0
    if name in ("wq", "wk"):
        # q_i ~ N(0, s^2) for unit-rms input; q.k/sqrt(D) has variance
        # s^4 D / D = s^4, so s = SCORE_VAR ** 0.25 per element.
        return SCORE_VAR ** 0.25 / math.sqrt(shape[-2])
    return 1.0 / math.sqrt(shape[-2])


def _make(key, spec: Spec) -> dict:
    dt = jnp.dtype(spec.dtype)
    shapes = _shapes(spec)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k, shape, jnp.float32)
        if name.startswith("norm"):
            out[name] = (1.0 + 0.1 * z).astype(dt)
        else:
            out[name] = (_std(name, shape) * z).astype(dt)
    return out


@functools.lru_cache(maxsize=None)
def _plain_fn(spec: Spec):
    return jax.jit(lambda key: _make(key, spec))


def plain_weights(spec: Spec, seed: int) -> dict:
    """The weights in the reference's layout, in the served dtype."""
    return _plain_fn(spec)(seed_key(seed))


def _to_program(w: dict, spec: Spec, padded_vocab: int) -> dict:
    pad = padded_vocab - spec.vocab
    return {
        "embed/table": jnp.pad(w["embed"], ((0, pad), (0, 0))),
        "blocks/norm_attn/scale": w["norm_attn"],
        "blocks/attn/wq": w["wq"], "blocks/attn/wk": w["wk"],
        "blocks/attn/wv": w["wv"], "blocks/attn/wo": w["wo"],
        "blocks/norm_ffn/scale": w["norm_ffn"],
        "blocks/mlp/w_gate": w["w_gate"], "blocks/mlp/w_up": w["w_up"],
        "blocks/mlp/w_down": w["w_down"],
        "norm_f/scale": w["norm_f"],
        "head/w": jnp.pad(w["head"], ((0, 0), (0, pad))),
    }


def program_weights(spec: Spec, seed: int, cfg) -> dict:
    """The same values as :func:`plain_weights`, as the program's
    parameter dict; refuses a layout that the program would not take."""
    from repro.models.model import init_params

    fn = jax.jit(lambda key: _to_program(_make(key, spec), spec,
                                         cfg.padded_vocab))
    want = jax.eval_shape(lambda k: init_params(cfg, k), seed_key(0))
    got = jax.eval_shape(fn, seed_key(0))
    shape_of = lambda t: {k: (v.shape, v.dtype) for k, v in t.items()}  # noqa: E731
    if shape_of(want) != shape_of(got):
        raise ValueError("the program's parameter layout changed: "
                         f"{shape_of(want)} != {shape_of(got)}")
    return fn(seed_key(seed))


# ---------------------------------------------------------------------------
# The reference and its fp8 control
# ---------------------------------------------------------------------------

def _rope(x, theta):
    """Rotary over the whole head, as two halves; x (T, H, D)."""
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _layer(x, w, i, *, spec, precision):
    at = lambda name: w[name][i]  # noqa: E731
    T = x.shape[0]
    h = rms(x, at("norm_attn"), spec.norm_eps)
    q = mm(h, at("wq"), precision).reshape(T, spec.n_heads, spec.head_dim)
    k = mm(h, at("wk"), precision).reshape(T, spec.n_kv_heads,
                                           spec.head_dim)
    v = mm(h, at("wv"), precision).reshape(T, spec.n_kv_heads,
                                           spec.head_dim)
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    x = x + mm(attention(q, k, v), at("wo"), precision)
    h = rms(x, at("norm_ffn"), spec.norm_eps)
    g = mm(h, at("w_gate"), precision)
    u = mm(h, at("w_up"), precision)
    return x + mm(jax.nn.silu(g) * u, at("w_down"), precision)


@functools.partial(jax.jit, static_argnames=("spec",))
def _embed(w, tokens, *, spec):
    return jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("spec", "precision"))
def _head(x, w, rows, *, spec, precision):
    h = rms(x[rows], w["norm_f"], spec.norm_eps)
    return mm(h, w["head"], precision)


def logits_at(w: dict, spec: Spec, tokens: Sequence[int],
              rows: Sequence[int], precision: str = "fp32") -> np.ndarray:
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


# ---------------------------------------------------------------------------
# The counts
# ---------------------------------------------------------------------------

def layer_weight_params(spec: Spec) -> int:
    d = spec.d_model
    return (d * spec.q_dim + 2 * d * spec.kv_dim + spec.q_dim * d
            + 3 * d * spec.d_ff + 2 * d)


def proj_flops_per_token(spec: Spec) -> int:
    d = spec.d_model
    per_layer = 2 * (d * spec.q_dim + 2 * d * spec.kv_dim + spec.q_dim * d
                     + 3 * d * spec.d_ff)
    return spec.n_layers * per_layer


def head_flops(spec: Spec) -> int:
    return 2 * spec.d_model * spec.vocab


def attn_flops(spec: Spec, pairs: int) -> int:
    """FLOPs of attention over ``pairs`` (query, key) pairs."""
    return spec.n_layers * 4 * spec.n_heads * spec.head_dim * pairs


def prefill_flops(spec: Spec, prompt_len: int) -> int:
    L = prompt_len
    return (L * proj_flops_per_token(spec) + attn_flops(spec, L * (L + 1) // 2)
            + head_flops(spec))


def decode_flops(spec: Spec, context: int) -> int:
    """One decode step whose new token attends to ``context`` tokens
    (itself included)."""
    return proj_flops_per_token(spec) + attn_flops(spec, context) \
        + head_flops(spec)


def weight_bytes(spec: Spec) -> int:
    b = dtype_bytes(spec.dtype)
    return b * (spec.n_layers * layer_weight_params(spec)
                + spec.d_model * spec.vocab + spec.d_model)


def kv_bytes_per_token(spec: Spec) -> int:
    return dtype_bytes(spec.dtype) * spec.n_layers * 2 * spec.kv_dim


def prefill_bytes(spec: Spec, prompt_len: int) -> int:
    b = dtype_bytes(spec.dtype)
    return (weight_bytes(spec) + prompt_len * b * spec.d_model
            + prompt_len * kv_bytes_per_token(spec))


def decode_bytes(spec: Spec, context: int) -> int:
    b = dtype_bytes(spec.dtype)
    return (weight_bytes(spec) + b * spec.d_model
            + context * kv_bytes_per_token(spec))
