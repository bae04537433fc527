"""A configuration file of the benchmark, read into the sizes it runs at.

``bench/configs/<config>.json`` holds the published config under the
published key names.  Where the program's block computes something else
than the published model (a norm, a rotary share, a bias), the file
lists it under ``departures``; every other key is run as published.
``arch`` names the program's model in its config registry and
``changes`` the fields of the program's config that the file sets on top
of it (the served dtype, the published rotary base, or everything for a
test's tiny model).  :func:`program_config` builds the
program's config and refuses it when it disagrees with the file.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib


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


def load_spec(path) -> Spec:
    raw = json.loads(pathlib.Path(path).read_text())
    heads = int(raw["num_attention_heads"])
    d = int(raw["hidden_size"])
    return Spec(
        name=pathlib.Path(path).stem,
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
