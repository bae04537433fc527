"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ca_matmul, gemm_mode
from repro.kernels import (ca_mmm_any, ca_mmm_k_outer, ca_mmm_kernel,
                           distance_product, ref)

SHAPES = [(128, 128, 128), (256, 128, 384), (128, 256, 128), (384, 384, 256)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int8]


def _rand(shape, dtype, seed):
    r = np.random.RandomState(seed)
    if jnp.dtype(dtype) == jnp.int8:
        return jnp.asarray(r.randint(-4, 5, shape), jnp.int8)
    return jnp.asarray(r.randn(*shape), dtype)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_kernel_vs_oracle(m, n, k, dtype):
    a = _rand((m, k), dtype, 0)
    b = _rand((k, n), dtype, 1)
    got = ca_mmm_kernel(a, b, bm=128, bn=128, bk=128, interpret=True)
    want = ref.ref_matmul(a, b)
    tol = 2e-2 if jnp.dtype(dtype) == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=str)
def test_k_outer_variant(dtype):
    a = _rand((256, 256), dtype, 2)
    b = _rand((256, 128), dtype, 3)
    got = ca_mmm_k_outer(a, b, bm=128, bn=128, bk=128, interpret=True)
    want = ref.ref_matmul(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(m=st.integers(1, 300), n=st.integers(1, 300), k=st.integers(1, 300))
def test_any_shape_pad_free(m, n, k):
    """Ragged shapes run natively (masked edge tiles, no HBM pad copies)."""
    a = _rand((m, k), jnp.float32, 4)
    b = _rand((k, n), jnp.float32, 5)
    got = ca_mmm_any(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_distance_product_semiring():
    a = _rand((65, 33), jnp.float32, 6)
    b = _rand((33, 47), jnp.float32, 7)
    got = distance_product(a, b, interpret=True)
    want = ref.ref_distance_product(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_trainable_vjp():
    a = _rand((96, 64), jnp.float32, 8)
    b = _rand((64, 80), jnp.float32, 9)
    with gemm_mode("interpret"):
        f = lambda a, b: (ca_matmul(a, b) ** 2).sum()
        ga, gb = jax.grad(f, argnums=(0, 1))(a, b)
    c = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(ga), 2 * c @ np.asarray(b).T,
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(a).T @ (2 * c),
                               rtol=1e-3, atol=1e-3)


def test_xla_and_interpret_paths_agree():
    a = _rand((130, 70), jnp.float32, 10)
    b = _rand((70, 90), jnp.float32, 11)
    with gemm_mode("xla"):
        y1 = ca_matmul(a, b)
    with gemm_mode("interpret"):
        y2 = ca_matmul(a, b)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Pallas flash attention (beyond-paper kernel) vs oracle
# ---------------------------------------------------------------------------

import jax as _jax
import jax.numpy as _jnp

from repro.kernels.flash_attn import flash_attention_tpu


@pytest.mark.parametrize("window", [None, 17], ids=["causal", "sliding"])
@pytest.mark.parametrize("gqa", [1, 4], ids=["mha", "gqa4"])
def test_flash_attention_kernel_vs_oracle(window, gqa):
    B, L, Hkv, D = 2, 100, 2, 32
    H = Hkv * gqa
    key = _jax.random.PRNGKey(0)
    q = _jax.random.normal(key, (B, L, H, D))
    k = _jax.random.normal(_jax.random.PRNGKey(1), (B, L, Hkv, D))
    v = _jax.random.normal(_jax.random.PRNGKey(2), (B, L, Hkv, D))
    pos = _jnp.broadcast_to(_jnp.arange(L, dtype=_jnp.int32)[None], (B, L))
    got = flash_attention_tpu(q, k, v, q_positions=pos, kv_positions=pos,
                              window=window, q_block=32, kv_block=32,
                              interpret=True)
    want = _jnp.stack([ref.ref_flash_attention(q[i], k[i], v[i], causal=True,
                                               window=window)
                       for i in range(B)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_kernel_block_invariance():
    B, L, H, D = 1, 64, 4, 16
    key = _jax.random.PRNGKey(3)
    q = _jax.random.normal(key, (B, L, H, D))
    k = _jax.random.normal(_jax.random.PRNGKey(4), (B, L, H, D))
    v = _jax.random.normal(_jax.random.PRNGKey(5), (B, L, H, D))
    pos = _jnp.broadcast_to(_jnp.arange(L, dtype=_jnp.int32)[None], (B, L))
    outs = [flash_attention_tpu(q, k, v, q_positions=pos, kv_positions=pos,
                                q_block=qb, kv_block=kb, interpret=True)
            for qb, kb in ((16, 16), (32, 64), (64, 64))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Paged int8 decode attention kernel vs oracle
# ---------------------------------------------------------------------------

from repro.kernels.flash_attn import paged_flash_attention_tpu


def _paged_pool(seed, lens, *, page, n_pages, Hkv, D, shuffle=True):
    """Quantize random fp32 K/V streams into a shuffled page pool.

    Returns (pool arrays..., per-seq dequantized fp K/V) so parity tests
    compare the kernel against the oracle on the *exact* values the int8
    pages hold — no quantization tolerance in the assert.
    """
    rng = np.random.RandomState(seed)
    B = len(lens)
    NP = max(-(-l // page) for l in lens)
    order = rng.permutation(n_pages) if shuffle else np.arange(n_pages)
    kp = np.zeros((n_pages, Hkv, page, D), np.int8)   # head-major pages
    vp = np.zeros((n_pages, Hkv, page, D), np.int8)
    ksc = np.zeros(n_pages, np.float32)
    vsc = np.zeros(n_pages, np.float32)
    tables = np.full((B, NP), -1, np.int32)
    deq_k, deq_v = [], []
    nxt = 0
    for b, L in enumerate(lens):
        kf = rng.randn(L, Hkv, D).astype(np.float32)
        vf = rng.randn(L, Hkv, D).astype(np.float32)
        npg = -(-L // page)
        pad = npg * page - L
        kfp = np.pad(kf, ((0, pad), (0, 0), (0, 0))).reshape(
            npg, page, Hkv, D).transpose(0, 2, 1, 3)
        vfp = np.pad(vf, ((0, pad), (0, 0), (0, 0))).reshape(
            npg, page, Hkv, D).transpose(0, 2, 1, 3)
        for j in range(npg):
            pid = order[nxt]
            nxt += 1
            tables[b, j] = pid
            for pool, scales, pages in ((kp, ksc, kfp), (vp, vsc, vfp)):
                sc = max(np.abs(pages[j]).max(), 1e-12) / 127.0
                pool[pid] = np.clip(np.round(pages[j] / sc), -127, 127
                                    ).astype(np.int8)
                scales[pid] = sc
        deq_k.append((kp[tables[b, :npg]].astype(np.float32)
                      * ksc[tables[b, :npg], None, None, None]
                      ).transpose(0, 2, 1, 3).reshape(npg * page, Hkv, D)[:L])
        deq_v.append((vp[tables[b, :npg]].astype(np.float32)
                      * vsc[tables[b, :npg], None, None, None]
                      ).transpose(0, 2, 1, 3).reshape(npg * page, Hkv, D)[:L])
    return (jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(ksc),
            jnp.asarray(vsc), jnp.asarray(tables),
            jnp.asarray(np.asarray(lens, np.int32)), deq_k, deq_v)


@pytest.mark.parametrize("window", [None, 11], ids=["causal", "sliding"])
@pytest.mark.parametrize("gqa", [1, 2], ids=["mha", "gqa2"])
def test_paged_attention_kernel_vs_oracle(window, gqa):
    """Ragged lengths crossing page boundaries, shuffled page ids."""
    Hkv, D, page = 2, 32, 8
    H = Hkv * gqa
    lens = [19, 27]  # both strictly inside their last (ragged) page
    kp, vp, ksc, vsc, tables, lens_j, deq_k, deq_v = _paged_pool(
        0, lens, page=page, n_pages=16, Hkv=Hkv, D=D)
    q = _jax.random.normal(_jax.random.PRNGKey(7), (len(lens), H, D))
    got = paged_flash_attention_tpu(q, kp, vp, ksc, vsc, tables, lens_j,
                                    window=window, interpret=True)
    for b, L in enumerate(lens):
        want = ref.ref_flash_attention(
            q[b][None], _jnp.asarray(deq_k[b]), _jnp.asarray(deq_v[b]),
            causal=True, window=window)[0]
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_paged_attention_freed_and_reused_pages():
    """A page reassigned to another sequence must not leak its previous
    tenant's keys: unmapped table slots (-1) and positions past ``len``
    are masked no matter what the page payload holds."""
    Hkv, D, page = 2, 16, 8
    lens = [9, 13]
    kp, vp, ksc, vsc, tables, lens_j, deq_k, deq_v = _paged_pool(
        1, lens, page=page, n_pages=8, Hkv=Hkv, D=D, shuffle=False)
    q = _jax.random.normal(_jax.random.PRNGKey(8), (len(lens), 2 * Hkv, D))
    base = paged_flash_attention_tpu(q, kp, vp, ksc, vsc, tables, lens_j,
                                     interpret=True)
    # Poison every page the tables do NOT map (freed pages with stale
    # garbage) and crank their scales: output must be bit-identical.
    mapped = set(np.asarray(tables).ravel().tolist()) - {-1}
    unmapped = [p for p in range(kp.shape[0]) if p not in mapped]
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    ksc2, vsc2 = np.asarray(ksc).copy(), np.asarray(vsc).copy()
    kp2[unmapped] = 127
    vp2[unmapped] = 127
    ksc2[unmapped] = 1e6
    vsc2[unmapped] = 1e6
    got = paged_flash_attention_tpu(
        q, _jnp.asarray(kp2), _jnp.asarray(vp2), _jnp.asarray(ksc2),
        _jnp.asarray(vsc2), tables, lens_j, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_paged_attention_matches_slab_flash():
    """Full-pool decode agrees with the dense flash kernel on the
    dequantized slab view of the same cache."""
    Hkv, D, page = 2, 32, 8
    lens = [24, 24]
    kp, vp, ksc, vsc, tables, lens_j, deq_k, deq_v = _paged_pool(
        2, lens, page=page, n_pages=8, Hkv=Hkv, D=D)
    B = len(lens)
    q = _jax.random.normal(_jax.random.PRNGKey(9), (B, 2 * Hkv, D))
    got = paged_flash_attention_tpu(q, kp, vp, ksc, vsc, tables, lens_j,
                                    interpret=True)
    k_slab = _jnp.stack([_jnp.asarray(x) for x in deq_k])
    v_slab = _jnp.stack([_jnp.asarray(x) for x in deq_v])
    qpos = (lens_j - 1)[:, None]
    kpos = _jnp.broadcast_to(_jnp.arange(lens[0], dtype=_jnp.int32)[None],
                             (B, lens[0]))
    want = flash_attention_tpu(q[:, None], k_slab, v_slab,
                               q_positions=qpos, kv_positions=kpos,
                               q_block=8, kv_block=8, interpret=True)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
