"""Operations and HBM bytes that serving needs, from a config's shapes.

What is counted is the work the request needs, not what a program
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

from .spec import Spec


def dtype_bytes(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


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
