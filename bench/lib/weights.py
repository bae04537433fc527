"""Random weights from the seed, made on the device in one jitted call.

The benchmark owns the weights: :func:`plain_weights` makes them in the
reference's plain layout, and :func:`program_weights` lays the same
values out as the program's parameter dict.  The reference makes its own
copy from the seed after the program is gone, so it takes nothing that
the program made.

The init law is the benchmark's: the embedding has unit variance, every
projection ``1/sqrt(fan_in)``, so that each layer's attention and MLP add
a term of the same order as the stream and the logits move with every
layer and with the cache.  ``q`` and ``k`` are scaled so that attention
scores have a standard deviation of 2, which makes attention prefer some
positions over others instead of averaging them all.  Norm gains are
``1 + 0.1 N(0, 1)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .spec import Spec

# Attention scores q.k/sqrt(head_dim) get this variance (see above).
SCORE_VAR = 4.0


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
