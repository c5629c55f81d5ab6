"""Sequence/context parallelism: ring attention + Ulysses parity.

Mirrors the framework's Life parity discipline (SURVEY §4): the sharded
implementation must match the single-device oracle on the virtual 8-device
CPU mesh, across shapes, dtypes, masks, and under differentiation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.parallel.context import (
    attention_reference,
    ring_attention,
    ulysses_attention,
)


def _qkv(rng, h, n, d, dtype=jnp.float32):
    shape = (h, n, d)
    q = jnp.asarray(rng.standard_normal(shape), dtype)
    k = jnp.asarray(rng.standard_normal(shape), dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    return q, k, v


@pytest.fixture(scope="module")
def sp_mesh():
    return mesh_lib.make_mesh_1d(8, axis="sp")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,n,d", [(4, 128, 32), (1, 64, 16), (3, 256, 8)])
def test_ring_attention_parity(rng, sp_mesh, causal, h, n, d):
    q, k, v = _qkv(rng, h, n, d)
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=causal)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,hkv,n,d", [(4, 4, 128, 32), (4, 2, 256, 16),
                                       (2, 1, 144, 8)])
def test_ring_attention_zigzag_parity(rng, sp_mesh, causal, h, hkv, n, d):
    """The striped/zigzag causal-balanced layout is bit-for-bit the same
    attention: operands permuted by zigzag_shard, outputs un-permuted by
    zigzag_unshard, must match the dense oracle on natural order — the
    positions the masks see are the layout's only degree of freedom."""
    from mpi_and_open_mp_tpu.parallel.context import (
        zigzag_shard, zigzag_unshard)

    p = sp_mesh.shape["sp"]
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
            for _ in range(2))
    qz, kz, vz = (zigzag_shard(x, p) for x in (q, k, v))
    got = zigzag_unshard(
        ring_attention(qz, kz, vz, mesh=sp_mesh, causal=causal,
                       layout="zigzag"), p)
    want = attention_reference(
        q, jnp.repeat(k, h // hkv, axis=0),
        jnp.repeat(v, h // hkv, axis=0), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [256, 272])  # 272: nl=34, half=17 -> padded
def test_ring_attention_zigzag_chunked(rng, sp_mesh, small_chunks, n):
    """Causal zigzag through the CHUNKED half-folders (fwd + grads): a
    tiny _Q_CHUNK forces the per-half q scans, 272 additionally makes
    the halves non-chunk-multiples so the padding rules fire."""
    from mpi_and_open_mp_tpu.parallel.context import (
        zigzag_shard, zigzag_unshard)

    small_chunks(8)
    p = sp_mesh.shape["sp"]
    h, hkv, d = 4, 2, 8
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
            for _ in range(2))
    qz, kz, vz = (zigzag_shard(x, p) for x in (q, k, v))

    got = zigzag_unshard(
        ring_attention(qz, kz, vz, mesh=sp_mesh, causal=True,
                       layout="zigzag"), p)
    want = attention_reference(
        q, jnp.repeat(k, h // hkv, axis=0),
        jnp.repeat(v, h // hkv, axis=0), causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss_zig(a, b, c):
        return jnp.sum(ring_attention(a, b, c, mesh=sp_mesh, causal=True,
                                      layout="zigzag") ** 2)

    def loss_nat(a, b, c):
        return jnp.sum(attention_reference(
            a, jnp.repeat(b, h // hkv, axis=0),
            jnp.repeat(c, h // hkv, axis=0), causal=True) ** 2)

    g_zig = jax.grad(loss_zig, argnums=(0, 1, 2))(qz, kz, vz)
    g_nat = jax.grad(loss_nat, argnums=(0, 1, 2))(q, k, v)
    for gz, gn in zip(g_zig, g_nat):
        np.testing.assert_allclose(np.asarray(zigzag_unshard(gz, p)),
                                   np.asarray(gn), rtol=1e-4, atol=1e-4)


def test_ring_attention_zigzag_grads(rng, sp_mesh):
    """Zigzag gradients through the ring flash backward match the dense
    oracle's, related by the zigzag permutation (dx_zig = dx_nat[perm])."""
    from mpi_and_open_mp_tpu.parallel.context import (
        zigzag_shard, zigzag_unshard)

    p = sp_mesh.shape["sp"]
    h, n, d = 2, 128, 16
    q, k, v = _qkv(rng, h, n, d)
    qz, kz, vz = (zigzag_shard(x, p) for x in (q, k, v))

    def loss_zig(a, b, c):
        return jnp.sum(ring_attention(a, b, c, mesh=sp_mesh, causal=True,
                                      layout="zigzag") ** 2)

    def loss_nat(a, b, c):
        return jnp.sum(attention_reference(a, b, c, causal=True) ** 2)

    g_zig = jax.grad(loss_zig, argnums=(0, 1, 2))(qz, kz, vz)
    g_nat = jax.grad(loss_nat, argnums=(0, 1, 2))(q, k, v)
    for gz, gn in zip(g_zig, g_nat):
        np.testing.assert_allclose(np.asarray(zigzag_unshard(gz, p)),
                                   np.asarray(gn), rtol=1e-4, atol=1e-4)


def test_ring_attention_zigzag_validation(rng, sp_mesh):
    from mpi_and_open_mp_tpu.parallel.context import (
        zigzag_order, zigzag_shard, zigzag_unshard)

    # seq 136 splits over 8 devices (17 each) but not into 16 half-chunks.
    q, k, v = _qkv(rng, 2, 136, 8)
    with pytest.raises(ValueError, match="zigzag"):
        ring_attention(q, k, v, mesh=sp_mesh, layout="zigzag")
    with pytest.raises(ValueError, match="unknown ring layout"):
        ring_attention(*_qkv(rng, 2, 128, 8), mesh=sp_mesh, layout="typo")
    # The permutation pair is an exact inverse.
    x = jnp.arange(3 * 64 * 4, dtype=jnp.float32).reshape(3, 64, 4)
    np.testing.assert_array_equal(
        np.asarray(zigzag_unshard(zigzag_shard(x, 8), 8)), np.asarray(x))
    # The cached permutations are frozen: a caller mutating the returned
    # array must fail loudly, not silently poison every later shard.
    with pytest.raises(ValueError):
        zigzag_order(64, 8)[0] = 1
    # Shard 0 of 4 owns half-chunks (0, 7): natural slots 0..7 and 56..63.
    order = np.asarray(zigzag_order(64, 4))
    np.testing.assert_array_equal(order[:16],
                                  list(range(8)) + list(range(56, 64)))


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_parity(rng, sp_mesh, causal):
    q, k, v = _qkv(rng, 8, 128, 32)
    got = ulysses_attention(q, k, v, mesh=sp_mesh, causal=causal)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_vs_ulysses_agree(rng, sp_mesh):
    q, k, v = _qkv(rng, 8, 256, 16)
    a = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    b = ulysses_attention(q, k, v, mesh=sp_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_bf16(rng, sp_mesh):
    # bf16 inputs, fp32 accumulation: loose tolerance vs the fp32 oracle.
    q, k, v = _qkv(rng, 2, 128, 32, dtype=jnp.bfloat16)
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    assert got.dtype == jnp.bfloat16
    want = attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want),
        rtol=0.05, atol=0.05)


def test_ring_attention_grad_parity(rng, sp_mesh):
    # Static ring trip count => fori_loop lowers to scan => reverse-mode
    # differentiable; gradients must match the oracle's.
    q, k, v = _qkv(rng, 2, 64, 16)

    def loss_sharded(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=sp_mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_attention_grad_parity(rng, sp_mesh):
    q, k, v = _qkv(rng, 8, 64, 16)

    def loss_sharded(q, k, v):
        return jnp.sum(
            ulysses_attention(q, k, v, mesh=sp_mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_output_sharded(rng, sp_mesh):
    # The result must stay sequence-sharded — no host gather mid-pipeline.
    q, k, v = _qkv(rng, 2, 128, 16)
    out = ring_attention(q, k, v, mesh=sp_mesh)
    assert len(out.sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in out.addressable_shards}
    assert shard_shapes == {(2, 16, 16)}


def test_seq_not_divisible_raises(rng, sp_mesh):
    q, k, v = _qkv(rng, 2, 100, 16)
    with pytest.raises(ValueError, match="not divisible"):
        ring_attention(q, k, v, mesh=sp_mesh)


def test_ulysses_heads_not_divisible_raises(rng, sp_mesh):
    q, k, v = _qkv(rng, 3, 128, 16)
    with pytest.raises(ValueError, match="heads not divisible"):
        ulysses_attention(q, k, v, mesh=sp_mesh)


@pytest.fixture
def small_chunks(monkeypatch):
    """Shrink _Q_CHUNK so the chunked paths run at test sizes, and clear
    the jit caches: the global is baked in at trace time and is NOT part
    of the cache key, so a stale trace from an unpatched test with the
    same signature would silently bypass the chunked code."""
    from mpi_and_open_mp_tpu.parallel import context

    def set_chunk(n):
        monkeypatch.setattr(context, "_Q_CHUNK", n)
        jax.clear_caches()

    yield set_chunk
    jax.clear_caches()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_chunked_parity(rng, sp_mesh, causal, small_chunks):
    small_chunks(16)  # n_local = 64 -> 4 chunks of 16
    q, k, v = _qkv(rng, 2, 512, 16)
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=causal)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_chunked_nondivisible(rng, sp_mesh, small_chunks):
    """n_local = 72 is not a multiple of the 16-row chunk: the padded-q
    path must still match (no divisibility cliff)."""
    small_chunks(16)
    q, k, v = _qkv(rng, 2, 576, 16)
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hq,hkv,n", [(4, 2, 512), (8, 1, 512),
                                      (4, 2, 456)])
def test_ring_gqa_folded_chunked_parity(rng, sp_mesh, hq, hkv, n,
                                        small_chunks):
    """Multi-hop ring with GQA folded rows AND per-fold q chunking (the
    un-expanded-K/V ring path), incl. gradients. n=456 makes n_local=57
    a NON-multiple of the chunk, exercising the g-scaled folded padding
    and the `nl * g` slice."""
    small_chunks(16)  # n_local = 64 (or 57) -> 4 folded chunks
    d = 8
    q = jnp.asarray(rng.standard_normal((hq, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    g = hq // hkv
    kr, vr = jnp.repeat(k, g, axis=0), jnp.repeat(v, g, axis=0)
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    want = attention_reference(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    g_got = jax.grad(
        lambda q_, k_, v_: jnp.sum(
            ring_attention(q_, k_, v_, mesh=sp_mesh, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(
        lambda q_, k_, v_: jnp.sum(attention_reference(
            q_, jnp.repeat(k_, g, axis=0), jnp.repeat(v_, g, axis=0),
            causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for gg, gw, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


def test_ulysses_attention_chunked_parity(rng, sp_mesh, small_chunks):
    small_chunks(32)  # n_global = 512 -> 16 chunks
    q, k, v = _qkv(rng, 8, 512, 16)
    got = ulysses_attention(q, k, v, mesh=sp_mesh, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_attention_chunked_nondivisible(rng, sp_mesh, small_chunks):
    """n_global = 520 pads to a chunk multiple; padded k positions must be
    masked out of the softmax, padded q rows discarded."""
    small_chunks(32)
    q, k, v = _qkv(rng, 8, 520, 16)
    got = ulysses_attention(q, k, v, mesh=sp_mesh, causal=False)
    want = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_single_device_ring_delegates_chunked(rng, small_chunks):
    """p=1 rings take the doubly-chunked local path (with causal k-block
    skipping) — parity incl. a non-multiple length."""
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib

    small_chunks(16)
    mesh1 = mesh_lib.make_mesh_1d(1, axis="sp")
    for n in (64, 72):
        q, k, v = _qkv(rng, 2, n, 8)
        got = ring_attention(q, k, v, mesh=mesh1, causal=True)
        want = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_chunked_grad_parity(rng, sp_mesh, causal,
                                            small_chunks):
    small_chunks(16)
    q, k, v = _qkv(rng, 2, 256, 8)

    def loss_sharded(q, k, v):
        return jnp.sum(
            ring_attention(q, k, v, mesh=sp_mesh, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_bf16_grad(rng, sp_mesh):
    """bf16 primals through the ring flash backward: bf16 grads out
    (f32 accumulation inside), loose tolerance vs the f32 oracle."""
    q, k, v = _qkv(rng, 2, 128, 16, dtype=jnp.bfloat16)

    def loss(q_, k_, v_):
        return jnp.sum(ring_attention(
            q_, k_, v_, mesh=sp_mesh, causal=True).astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda a, b, c: jnp.sum(attention_reference(a, b, c, causal=True)
                                ** 2),
        argnums=(0, 1, 2))(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    for got, want, nm in zip(g, gf, "qkv"):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                                   np.asarray(want), rtol=0.1, atol=0.1,
                                   err_msg=f"d{nm}")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n", [96, 72])
def test_flash_backward_parity(rng, causal, n, small_chunks):
    """The custom flash backward (recompute-from-logsumexp, two chunked
    passes) must match autodiff of the dense oracle — including causal
    block skipping and a non-multiple length (n=72 pads the last chunk)."""
    from mpi_and_open_mp_tpu.parallel.context import _attention_chunked

    small_chunks(16)
    q, k, v = _qkv(rng, 3, n, 8)

    def loss_flash(q, k, v):
        return jnp.sum(_attention_chunked(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4,
            err_msg=f"d{name}")


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_folded_parity(rng, hq, hkv, causal, small_chunks):
    """The GQA fold path (query groups folded into the row axis, K/V
    un-expanded) through the flash forward AND custom backward, at a
    chunked non-multiple length — vs the dense oracle on repeated K/V."""
    from mpi_and_open_mp_tpu.parallel.context import _attention_chunked

    small_chunks(16)
    n, d = 72, 8
    q = jnp.asarray(rng.standard_normal((hq, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    g = hq // hkv

    def loss_flash(q_, k_, v_):
        return jnp.sum(_attention_chunked(q_, k_, v_, causal) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(
            q_, jnp.repeat(k_, g, axis=0), jnp.repeat(v_, g, axis=0),
            causal=causal) ** 2)

    got = _attention_chunked(q, k, v, causal)
    want = attention_reference(q, jnp.repeat(k, g, axis=0),
                               jnp.repeat(v, g, axis=0), causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gg, gw, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


def test_flash_backward_bf16_dtypes(rng, small_chunks):
    """bf16 primals get bf16 gradients (f32 accumulation inside)."""
    from mpi_and_open_mp_tpu.parallel.context import _attention_chunked

    small_chunks(16)
    q, k, v = _qkv(rng, 2, 64, 8, dtype=jnp.bfloat16)
    g = jax.grad(
        lambda q_: jnp.sum(_attention_chunked(
            q_, k, v, True).astype(jnp.float32) ** 2))(q)
    assert g.dtype == jnp.bfloat16
    gf = jax.grad(
        lambda q_: jnp.sum(attention_reference(
            q_.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True) ** 2))(q.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(g, dtype=np.float32),
                               np.asarray(gf), rtol=0.1, atol=0.1)


def test_flash_backward_residuals_bounded(rng, small_chunks):
    """The flash backward's memory contract: grad of an (unrolled) chain
    of chunked-attention calls must not materialise any O(seq²) array —
    residuals are (q, k, v, o, logsumexp) per call, recompute does the
    rest. Checked structurally on the jaxpr (every intermediate shape
    bounded below the full score matrix), which is what OOM'd on real
    HBM before the custom_vjp existed."""
    import re
    from functools import reduce

    from mpi_and_open_mp_tpu.parallel.context import _attention_chunked

    small_chunks(16)
    h, n, d = 2, 96, 8
    q, k, v = _qkv(rng, h, n, d)

    def loss(q_):
        c = q_
        for _ in range(3):
            c = _attention_chunked(c, k, v, True)
        return jnp.sum(c ** 2)

    s = str(jax.make_jaxpr(jax.grad(loss))(q))
    score_elems = h * n * n  # full (h, n, n) score matrix
    for m in set(re.findall(r"(?:f32|f16|bf16|bool|pred)\[([0-9,]+)\]", s)):
        dims = [int(x) for x in m.split(",") if x]
        assert reduce(lambda a, b: a * b, dims, 1) < score_elems, (
            f"O(seq^2) intermediate [{m}] in the flash-backward jaxpr")


def test_ring_backward_no_mask_residuals(rng, sp_mesh):
    """The ring backward remats its block updates with the allow-mask
    built INSIDE from position vectors: no boolean mask of block size
    (h, n_local, n_local) may survive as a saved residual in the grad
    jaxpr — a passed-in mask used to be stacked across hops."""
    import re
    from functools import reduce

    h, n, d = 2, 256, 8
    nl = n // 8
    q, k, v = _qkv(rng, h, n, d)

    def loss(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh=sp_mesh,
                                      causal=True) ** 2)

    s = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    block_elems = h * nl * nl
    for m in set(re.findall(r"(?:bool|pred)\[([0-9,]+)\]", s)):
        dims = [int(x) for x in m.split(",") if x]
        # One live block mask (the in-backward recompute) is fine; a
        # hop-stacked residual (p, h, nl, nl) is the regression.
        assert reduce(lambda a, b: a * b, dims, 1) <= block_elems, (
            f"stacked mask boolean [{m}] in the ring-backward jaxpr")


def test_ring_flash_backward_residuals_bounded(rng, sp_mesh):
    """The ring backward's memory contract: custom_vjp residuals are
    (q, k, v, o, logsumexp) per shard and the backward recomputes one
    (h, n_local, n_local) block at a time while counter-rotating K/V —
    so NO intermediate in the sharded grad jaxpr may exceed one block
    (= here also the global input size). A hop-stacked residual
    (p, h, nl, nl) — what remat-autodiff used to linearise out of the
    fori_loop — is an order of magnitude over the bound."""
    import re
    from functools import reduce

    h, n, d = 2, 512, 8
    nl = n // 8
    q, k, v = _qkv(rng, h, n, d)

    def loss(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh=sp_mesh,
                                      causal=True) ** 2)

    s = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    block_elems = h * nl * nl
    for m in set(re.findall(r"(?:f32|f16|bf16|bool|pred)\[([0-9,]+)\]", s)):
        dims = [int(x) for x in m.split(",") if x]
        assert reduce(lambda a, b: a * b, dims, 1) <= block_elems, (
            f"intermediate [{m}] exceeds one score block in the ring "
            "flash-backward jaxpr")


def test_ulysses_chunked_grad_parity(rng, sp_mesh, small_chunks):
    """The flash backward through shard_map + all_to_all (the Ulysses
    training path)."""
    small_chunks(16)
    q, k, v = _qkv(rng, 8, 256, 8)

    def loss_sharded(q, k, v):
        return jnp.sum(
            ulysses_attention(q, k, v, mesh=sp_mesh, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    g_got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hkv", [1, 2, 8])
def test_gqa_kv_head_broadcast(rng, sp_mesh, hkv):
    """GQA/MQA: fewer K/V heads broadcast across query-head groups, for
    both variants, vs an oracle fed the explicitly repeated K/V.
    hkv=8 with hq=16 exercises Ulysses' un-expanded-on-the-wire path
    (hkv % p == 0); hkv in {1, 2} exercises its pre-expansion fallback
    and the ring's per-fold local expansion."""
    hq, n, d = (16, 128, 16) if hkv == 8 else (8, 128, 16)
    q = jnp.asarray(rng.standard_normal((hq, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    kr = jnp.repeat(k, hq // hkv, axis=0)
    vr = jnp.repeat(v, hq // hkv, axis=0)
    want = attention_reference(q, kr, vr, causal=True)
    for fn in (ring_attention, ulysses_attention):
        got = fn(q, k, v, mesh=sp_mesh, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_gqa_indivisible_heads_raises(rng, sp_mesh):
    q, k, v = _qkv(rng, 8, 128, 16)
    with pytest.raises(ValueError, match="not a multiple"):
        ring_attention(q, k[:3], v[:3], mesh=sp_mesh)


def test_flash_attention_public_api(rng, small_chunks):
    """The exported single-device flash engine: chunked, GQA, grads."""
    from mpi_and_open_mp_tpu.parallel import flash_attention

    small_chunks(16)
    q = jnp.asarray(rng.standard_normal((4, 72, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 72, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 72, 8)), jnp.float32)
    got = flash_attention(q, k, v, causal=True)
    want = attention_reference(q, jnp.repeat(k, 2, axis=0),
                               jnp.repeat(v, 2, axis=0), causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    k3 = jnp.asarray(rng.standard_normal((3, 72, 8)), jnp.float32)
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention(q, k3, k3)


def test_pallas_dispatch_routing(rng, monkeypatch):
    """The TPU flash-kernel dispatch predicate: routes only equal-head,
    128-multiple-seq, MXU-width-dim, matching-float shapes, only on a
    TPU backend, and only while the engine flag is up — the CPU/oracle
    path must never see the Pallas kernel."""
    from mpi_and_open_mp_tpu.parallel import context

    def qkv(hq=4, hkv=4, n=1024, d=128, dt=jnp.bfloat16, kdt=None):
        q = jnp.zeros((hq, n, d), dt)
        k = jnp.zeros((hkv, n, d), kdt or dt)
        return q, k, jnp.zeros((hkv, n, d), kdt or dt)

    # On the real (cpu) test backend: never eligible.
    assert not context._pallas_flash_eligible(*qkv())

    monkeypatch.setattr(context.jax, "default_backend", lambda: "tpu")
    assert context._pallas_flash_eligible(*qkv())
    # GQA is never DIRECTLY eligible (the kernel wants equal heads)...
    assert not context._pallas_flash_eligible(*qkv(hkv=2))
    # ...but the dispatch plan expands budget-fitting K/V to reach the
    # kernel (chip-measured ~2.7x over the folded jnp path), and the
    # provenance stamp says so.
    assert context._flash_dispatch_plan(*qkv(hkv=2)) == (
        "expand", 1024, 1024, 2)
    assert context.flash_engine_for(*qkv(hkv=2)) == "pallas:b1024:kvx2"
    # Over the expand budget (2 GiB combined K+V) GQA stays on the
    # folded jnp engine. Shape probes only — nothing this size is
    # allocated.
    big = [jax.ShapeDtypeStruct((h, 1 << 20, 128), jnp.bfloat16)
           for h in (8, 2, 2)]
    assert context._flash_dispatch_plan(*big) is None
    assert context.flash_engine_for(*big) == "jnp"
    assert not context._pallas_flash_eligible(*qkv(n=1000))  # seq % 128
    assert not context._pallas_flash_eligible(*qkv(d=64))  # head dim
    assert not context._pallas_flash_eligible(
        *qkv(dt=jnp.float16))  # dtype
    assert not context._pallas_flash_eligible(
        *qkv(kdt=jnp.float32))  # mixed dtypes
    # Auto block: largest chip-validated edge dividing the sequence
    # within the b*d budget AND leaving >= _MIN_GRID programs per grid
    # axis (8k at b1024 measured an 8x8-grid backward collapse — see the
    # _MIN_GRID note), stamped into the shape-aware provenance.
    assert context._flash_block_for(32768) == 1024
    assert context._flash_block_for(16384) == 1024  # grid floor exactly met
    assert context._flash_block_for(8192) == 512  # b1024 would leave 8x8
    # The floor applies at EVERY edge now (the 8k starvation finding
    # extrapolates: a starved grid is a grid property, not a b1024
    # property), so 2k-4k step down to the occupancy-floored edge.
    assert context._flash_block_for(4096) == 256
    assert context._flash_block_for(2048) == 128
    # Sequences too short for ANY edge to form a _MIN_GRID grid take the
    # largest fitting block rather than drop to jnp.
    assert context._flash_block_for(1536) == 512
    assert context._flash_block_for(1280) == 256
    assert context._flash_block_for(384) == 128
    assert context._flash_block_for(32768, d=256) == 512  # budget scales
    assert context._flash_block_for(32768, d=1024) == 128
    assert context._flash_block_for(32768, d=2048) == 0  # no block fits
    assert not context._pallas_flash_eligible(*qkv(d=2048))
    assert context.flash_engine_for(*qkv(n=1024)) == "pallas:b1024"
    assert context.flash_engine_for(*qkv(n=1000)) == "jnp"
    # At or below the chunk size the dispatch short-circuits to the
    # dense reference before any engine — provenance must say so.
    assert context.flash_engine_for(*qkv(n=512)) == "dense"

    # The gate's module-internal force pins the auto choice (so a small
    # gate run exercises a larger timed sequence's configuration)...
    monkeypatch.setattr(context, "_FORCED_BLOCK", 512)
    assert context._flash_block_for(32768) == 512

    # Block-size override tightens the divisibility requirement.
    monkeypatch.setenv("MOMP_FLASH_BLOCK", "512")
    assert context._pallas_flash_eligible(*qkv(n=1024))
    assert not context._pallas_flash_eligible(*qkv(n=1280))  # % 512
    monkeypatch.setattr(context, "_FORCED_BLOCK", 256)
    assert context._flash_block_for(32768) == 512  # ...but env wins
    monkeypatch.setattr(context, "_FORCED_BLOCK", 0)
    # Bad knob values fail loudly with the knob's name, once.
    for bad in ("128k", "96", "-128"):
        monkeypatch.setenv("MOMP_FLASH_BLOCK", bad)
        with pytest.raises(ValueError, match="MOMP_FLASH_BLOCK"):
            context._flash_block_override()
    monkeypatch.delenv("MOMP_FLASH_BLOCK")

    # The backward edge is decoupled: its own knob pins the eight
    # dq/dkv blocks while the forward keeps its auto choice, and the
    # provenance stamp carries both only when they differ.
    monkeypatch.setenv("MOMP_FLASH_BLOCK_BWD", "512")
    assert context._flash_block_for(32768) == 1024
    assert context._flash_bwd_block_for(32768) == 512
    assert context._flash_dispatch_plan(*qkv(n=1024)) == (
        "direct", 1024, 512, 1)
    assert context.flash_engine_for(*qkv(n=1024)) == "pallas:b1024:bw512"
    # ...and the backward edge tightens divisibility on its own axis.
    assert not context._pallas_flash_eligible(*qkv(n=1280))  # % 512
    for bad in ("64", "100"):
        monkeypatch.setenv("MOMP_FLASH_BLOCK_BWD", bad)
        with pytest.raises(ValueError, match="MOMP_FLASH_BLOCK_BWD"):
            context._flash_block_override_bwd()
    monkeypatch.delenv("MOMP_FLASH_BLOCK_BWD")
    # The gate's module-internal backward force mirrors the env knob;
    # unpinned, the backward follows the forward choice exactly.
    monkeypatch.setattr(context, "_FORCED_BLOCK_BWD", 256)
    assert context._flash_bwd_block_for(32768) == 256
    monkeypatch.setattr(context, "_FORCED_BLOCK_BWD", 0)
    assert context._flash_bwd_block_for(32768) == 1024

    monkeypatch.setattr(context, "_TPU_FLASH", False)
    assert not context._pallas_flash_eligible(*qkv())  # kill switch


def test_gated_parity_check_cpu():
    """The recorders' shared honesty gate on the CPU (jnp) engine:
    passes clean for equal-head and GQA/MQA configurations — the GQA
    form checks the gate's group-summed oracle gradients — and reports
    the engine the timed shape will use."""
    from mpi_and_open_mp_tpu.parallel import context

    ok, engine, notes = context.gated_parity_check(n=640)
    assert ok and engine == "jnp" and notes == []
    ok, engine, notes = context.gated_parity_check(n=640, kv_heads=2)
    assert ok and engine == "jnp" and notes == []
    # MQA, with a for_seq (no-op off-TPU: flag-level engine is jnp).
    ok, engine, _ = context.gated_parity_check(
        n=640, kv_heads=1, for_seq=32768)
    assert ok and engine == "jnp"


def test_ring_attention_default_mesh(rng):
    q, k, v = _qkv(rng, 2, 64, 8)
    got = ring_attention(q, k, v, causal=False)
    want = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The per-hop Pallas ring engine (tentpole): routing, merge math, and
# end-to-end interpret-mode parity on the virtual mesh.


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Force the Pallas engine in interpret mode: flip the trace-time
    module flag and clear jit caches on both sides — the flag is not
    part of any jit cache key, so stale traces from the other setting
    must not be reused (in either direction)."""
    from mpi_and_open_mp_tpu.parallel import context

    jax.clear_caches()
    monkeypatch.setattr(context, "_PALLAS_INTERPRET", True)
    yield context
    jax.clear_caches()


def test_merge_partials_exact(rng):
    """The online-softmax combine of two NORMALISED partials over
    disjoint key sets is the softmax over their union — the identity
    that lets per-hop flash partials fold in any order. Checked exactly
    against the one-shot softmax, and for associativity."""
    from mpi_and_open_mp_tpu.parallel.context import _merge_partials

    h, n, m, d = 2, 16, 24, 8
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((h, m, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((h, m, d)), jnp.float32)

    def partial(ks, vs):
        s = jnp.einsum("hqd,hkd->hqk", q, ks) / np.sqrt(d)
        L = jax.scipy.special.logsumexp(s, axis=-1)
        o = jnp.einsum("hqk,hkd->hqd", jnp.exp(s - L[..., None]), vs)
        return o, L

    o1, L1 = partial(k[:, :10], v[:, :10])
    o2, L2 = partial(k[:, 10:18], v[:, 10:18])
    o3, L3 = partial(k[:, 18:], v[:, 18:])
    want_o, want_L = partial(k, v)

    o12, L12 = _merge_partials(o1, L1, o2, L2)
    got_o, got_L = _merge_partials(o12, L12, o3, L3)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_L), np.asarray(want_L),
                               rtol=1e-6, atol=1e-6)
    # Associative: fold (2,3) first instead.
    o23, L23 = _merge_partials(o2, L2, o3, L3)
    alt_o, alt_L = _merge_partials(o1, L1, o23, L23)
    np.testing.assert_allclose(np.asarray(alt_o), np.asarray(got_o),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(alt_L), np.asarray(got_L),
                               rtol=1e-6, atol=1e-6)


def test_ring_hop_engine_routing(monkeypatch):
    """ring_hop_engine_for: per-hop provenance judged at per-SHARD
    granularity — the kernel on eligible hop blocks (GQA via the expand
    form), the jnp fold for causal zigzag / ineligible hop shapes /
    under the MOMP_RING_HOP kill switch, the local engine at p=1."""
    from mpi_and_open_mp_tpu.parallel import context

    def qkv(h=4, hkv=4, n=8192, d=128):
        q = jnp.zeros((h, n, d), jnp.bfloat16)
        k = jnp.zeros((hkv, n, d), jnp.bfloat16)
        return q, k, jnp.zeros((hkv, n, d), jnp.bfloat16)

    # On the real (cpu) test backend hops are jnp — same predicate as
    # the local dispatch, applied to the hop block shape.
    assert context.ring_hop_engine_for(*qkv(), p=8) == "jnp"

    monkeypatch.setattr(context.jax, "default_backend", lambda: "tpu")
    # 8k global over 8 devices -> 1k hop blocks. p >= 3 rings run the
    # double-slot hop prefetch by default and the stamp says so.
    assert context.ring_hop_engine_for(*qkv(), p=8) == "pallas:b1024:pf"
    # GQA hops expand locally per hop; the stamp says so.
    assert (context.ring_hop_engine_for(*qkv(hkv=2), p=8)
            == "pallas:b1024:kvx2:pf")
    # Causal zigzag decomposes each hop into half-chunk kernel calls:
    # eligibility and block edge are judged on the (h, nl/2, d) half
    # shape and the stamp says so (1k hop blocks -> 512 halves).
    # Non-causal zigzag has no masks, so it takes the contiguous form.
    assert context.ring_hop_engine_for(
        *qkv(), p=8, causal=True, layout="zigzag") == "pallas:b512:zz:pf"
    assert context.ring_hop_engine_for(
        *qkv(), p=8, causal=False, layout="zigzag") == "pallas:b1024:pf"
    # MOMP_RING_ZZ=0 pins causal zigzag (and only it) to the jnp fold.
    monkeypatch.setattr(context, "_RING_ZZ", False)
    assert context.ring_hop_engine_for(
        *qkv(), p=8, causal=True, layout="zigzag") == "jnp"
    assert context.ring_hop_engine_for(*qkv(), p=8) == "pallas:b1024:pf"
    monkeypatch.setattr(context, "_RING_ZZ", True)
    # MOMP_RING_PREFETCH=0 drops back to the single-slot schedule (and
    # only that — the hop kernel stays); a 2-device ring has a single
    # transfer, so it never stamps :pf regardless of the gate.
    monkeypatch.setattr(context, "_RING_PREFETCH", False)
    assert context.ring_hop_engine_for(*qkv(), p=8) == "pallas:b1024"
    monkeypatch.setattr(context, "_RING_PREFETCH", True)
    assert context.ring_hop_engine_for(*qkv(n=2048), p=2) \
        == "pallas:b1024"
    # Hop blocks that fail the kernel predicate (seq % 128) fall back.
    assert context.ring_hop_engine_for(*qkv(n=8 * 1000), p=8) == "jnp"
    # A 1-device ring never enters the ring body: local provenance.
    assert (context.ring_hop_engine_for(*qkv(), p=1)
            == "local:pallas:b512")
    # Kill switch pins the ring to the jnp fold oracle.
    monkeypatch.setattr(context, "_RING_HOP", False)
    assert context.ring_hop_engine_for(*qkv(), p=8) == "jnp"


def test_ring_hop_bwd_engine_routing(monkeypatch):
    """ring_hop_bwd_engine_for: the ring BACKWARD's per-hop provenance —
    the repo-owned hop kernels on eligible contiguous hop shapes (edge
    capped at flash_hop_bwd.MAX_BLOCK), the jnp _flash_block_grads fold
    for causal zigzag / ineligible shapes / under MOMP_RING_HOP_BWD=0
    or MOMP_RING_HOP=0, the local engine at p=1."""
    from mpi_and_open_mp_tpu.parallel import context

    def qkv(h=4, hkv=4, n=8192, d=128):
        q = jnp.zeros((h, n, d), jnp.bfloat16)
        k = jnp.zeros((hkv, n, d), jnp.bfloat16)
        return q, k, jnp.zeros((hkv, n, d), jnp.bfloat16)

    assert context.ring_hop_bwd_engine_for(*qkv(), p=8) == "jnp"

    monkeypatch.setattr(context.jax, "default_backend", lambda: "tpu")
    # 1k hop blocks: the forward edge is b1024, the hop backward caps
    # at the kernels' VMEM-budget MAX_BLOCK (512). The K/V trip
    # prefetches exactly as the forward's — the stamp carries :pf.
    assert context.ring_hop_bwd_engine_for(*qkv(), p=8) == "pallas:b512:pf"
    # GQA hops expand per hop, like the forward engine.
    assert (context.ring_hop_bwd_engine_for(*qkv(hkv=2), p=8)
            == "pallas:b512:kvx2:pf")
    # Causal zigzag gradients stay on the jnp fold (the half-chunk
    # decomposition is forward-only); non-causal zigzag is maskless.
    assert context.ring_hop_bwd_engine_for(
        *qkv(), p=8, causal=True, layout="zigzag") == "jnp"
    assert context.ring_hop_bwd_engine_for(
        *qkv(), p=8, causal=False, layout="zigzag") == "pallas:b512:pf"
    # MOMP_RING_PREFETCH=0: single-slot K/V trip, kernel hops stay.
    monkeypatch.setattr(context, "_RING_PREFETCH", False)
    assert context.ring_hop_bwd_engine_for(*qkv(), p=8) == "pallas:b512"
    monkeypatch.setattr(context, "_RING_PREFETCH", True)
    assert context.ring_hop_bwd_engine_for(*qkv(n=8 * 1000), p=8) == "jnp"
    assert (context.ring_hop_bwd_engine_for(*qkv(), p=1)
            == "local:pallas:b512")
    # MOMP_RING_HOP_BWD=0: backward hops fold, forward hops keep the
    # kernel. MOMP_RING_HOP=0 pins both.
    monkeypatch.setattr(context, "_RING_HOP_BWD", False)
    assert context.ring_hop_bwd_engine_for(*qkv(), p=8) == "jnp"
    assert context.ring_hop_engine_for(*qkv(), p=8) == "pallas:b1024:pf"
    monkeypatch.setattr(context, "_RING_HOP_BWD", True)
    monkeypatch.setattr(context, "_RING_HOP", False)
    assert context.ring_hop_bwd_engine_for(*qkv(), p=8) == "jnp"


def test_ring_hop_pinned_pins_both_directions():
    """The chaos-recovery pin (_ring_hop_pinned(False)) must pin BOTH
    hop engines AND the hop prefetch: the :recovered re-dispatch
    promises the full single-slot jnp fold oracle, forward and
    backward."""
    from mpi_and_open_mp_tpu.parallel import context

    assert context._RING_HOP and context._RING_HOP_BWD
    assert context._RING_PREFETCH
    with context._ring_hop_pinned(False):
        assert not context._RING_HOP
        assert not context._RING_HOP_BWD
        assert not context._RING_PREFETCH
        assert not context._ring_prefetch_on(8)
    assert context._RING_HOP and context._RING_HOP_BWD
    assert context._RING_PREFETCH


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hkv", [4, 2])
def test_ring_hop_flash_interpret_parity(rng, sp_mesh, pallas_interpret,
                                         causal, hkv):
    """End-to-end ring attention with the per-hop Pallas engine engaged
    (interpret mode, 8-virtual-device mesh): forward AND grads must
    match both the dense oracle and the jnp fold it replaced. hkv=2
    exercises the per-hop GQA expand and the folded-L handoff to the
    travelling-dk/dv backward."""
    context = pallas_interpret
    h, n, d = 4, 8 * 128, 128  # 128-per-shard hops: interpret-eligible
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    sp_mesh_p = sp_mesh.shape["sp"]

    stamp = context.ring_hop_engine_for(q, k, v, p=sp_mesh_p, causal=causal)
    assert stamp == ("pallas:b128:pf" if hkv == h
                     else "pallas:b128:kvx2:pf")

    kr = jnp.repeat(k, h // hkv, axis=0)
    vr = jnp.repeat(v, h // hkv, axis=0)

    got = ring_attention(q, k, v, mesh=sp_mesh, causal=causal)
    want = attention_reference(q, kr, vr, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

    # Against the jnp fold oracle it replaced (kill switch flips the
    # trace-time routing; caches cleared so the flip is honoured).
    try:
        context._RING_HOP = False
        jax.clear_caches()
        fold = ring_attention(q, k, v, mesh=sp_mesh, causal=causal)
    finally:
        context._RING_HOP = True
        jax.clear_caches()
    np.testing.assert_allclose(np.asarray(got), np.asarray(fold),
                               rtol=1e-4, atol=1e-4)

    # Grads: the hop engine feeds its merged (o, L) into the same
    # travelling-dk/dv ring backward (the kernel's own vjp is never
    # entered — it is broken under 0.4.37 interpret, so passing proves
    # the custom_vjp contract held).
    def loss(fn, q_, k_, v_):
        return jnp.sum(fn(q_, k_, v_) ** 2)

    g_got = jax.grad(loss, argnums=(1, 2, 3))(
        lambda a, b, c: ring_attention(a, b, c, mesh=sp_mesh,
                                       causal=causal), q, k, v)
    g_want = jax.grad(loss, argnums=(1, 2, 3))(
        lambda a, b, c: attention_reference(
            a, jnp.repeat(b, h // hkv, axis=0),
            jnp.repeat(c, h // hkv, axis=0), causal=causal), q, k, v)
    for got_g, want_g in zip(g_got, g_want):
        assert got_g.shape == want_g.shape
        np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g),
                                   rtol=1e-3, atol=1e-3)


def test_ring_prefetch_matches_single_slot_schedule(rng, sp_mesh,
                                                    pallas_interpret):
    """The double-slot K/V hop prefetch (``:pf``) against the single-slot
    schedule it deepens (MOMP_RING_PREFETCH=0), interpret mode on the
    8-virtual-device mesh. Both run the same folds in the same order;
    only the rotation issue points move, so the forward must be
    bit-identical and the gradients equal, and the stamps must say which
    schedule ran."""
    context = pallas_interpret
    h, n, d = 4, 8 * 128, 128  # 128-per-shard hops: interpret-eligible
    q, k, v = _qkv(rng, h, n, d)
    p = sp_mesh.shape["sp"]

    def ring(q_, k_, v_):
        return ring_attention(q_, k_, v_, mesh=sp_mesh, causal=True)

    def loss(q_, k_, v_):
        return jnp.sum(ring(q_, k_, v_) ** 2)

    def leg():
        stamps = (context.ring_hop_engine_for(q, k, v, p=p, causal=True),
                  context.ring_hop_bwd_engine_for(q, k, v, p=p,
                                                  causal=True))
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return (stamps, np.asarray(ring(q, k, v)),
                [np.asarray(g) for g in grads])

    (pf, pf_bwd), pf_fwd, pf_grads = leg()
    assert pf == pf_bwd == "pallas:b128:pf"
    np.testing.assert_allclose(
        pf_fwd, np.asarray(attention_reference(q, k, v, causal=True)),
        rtol=1e-4, atol=1e-4)
    try:
        context._RING_PREFETCH = False
        jax.clear_caches()
        (nopf, nopf_bwd), nopf_fwd, nopf_grads = leg()
    finally:
        context._RING_PREFETCH = True
        jax.clear_caches()
    assert nopf == nopf_bwd == "pallas:b128"
    np.testing.assert_array_equal(nopf_fwd, pf_fwd)
    for name, a, b in zip("dq dk dv".split(), pf_grads, nopf_grads):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6, err_msg=name)


def test_pallas_flash_interpret_shard_map_single_device(rng,
                                                        pallas_interpret):
    """A 1-device sp mesh with the Pallas dispatch force-engaged
    (interpret mode): shard_map + _pallas_flash must compile together
    and match the dense oracle — the minimal on-chip local dispatch,
    runnable without hardware. Forward only: 0.4.37's interpret
    discharge rule breaks in the kernel backward, which is exactly why
    the ring keeps its own custom_vjp."""
    context = pallas_interpret
    h, n, d = 2, 1024, 128  # n > _Q_CHUNK so the dense short-circuit
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)

    # Interpret mode skips the backend check but still wants blk == seq.
    assert context.flash_engine_for(q, k, v) == "pallas:b1024"
    mesh1 = mesh_lib.make_mesh_1d(1, axis="sp")
    got = ring_attention(q, k, v, mesh=mesh1, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# The ring BACKWARD hop kernels and the causal-zigzag forward hop
# dispatch (tentpole): block-level kernel parity vs the jnp oracle
# arithmetic, end-to-end interpret parity on the virtual mesh, and the
# MOMP_RING_HOP_BWD / MOMP_RING_ZZ escape hatches.


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blk", [128, 256])
def test_hop_flash_block_grads_kernel_parity(rng, causal, blk):
    """ops.flash_hop_bwd.hop_block_grads (interpret mode, multi-tile
    grids included) against _flash_block_grads — THE jnp oracle
    arithmetic every ring hop gradient folds with. Same L/D statistics,
    same masking semantics, so the kernels may replace the fold
    block-for-block."""
    from mpi_and_open_mp_tpu.ops import flash_hop_bwd
    from mpi_and_open_mp_tpu.parallel.context import (
        _flash_block_grads, _mask_from_pos)

    h, n, d = 2, 256, 128
    scale = 1.0 / np.sqrt(d)
    q, k, v, do = (jnp.asarray(rng.standard_normal((h, n, d)),
                               jnp.float32) for _ in range(4))
    s = jnp.einsum("hqd,hkd->hqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    L = jax.scipy.special.logsumexp(s, axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", jnp.exp(s - L[..., None]), v)
    D = jnp.sum(do * o, axis=-1)

    pos = jnp.arange(n)
    mask = _mask_from_pos(pos, pos, None, causal)
    want = _flash_block_grads(q, do, L, D, k, v, mask, scale)
    got = flash_hop_bwd.hop_block_grads(
        q, do, flash_hop_bwd.lane_broadcast(L),
        flash_hop_bwd.lane_broadcast(D), k, v, causal=causal, blk=blk,
        interpret=True)
    for name, a, b in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("hkv", [4, 2])
def test_ring_hop_bwd_kill_switch_matches_kernel(rng, sp_mesh,
                                                 pallas_interpret, hkv):
    """MOMP_RING_HOP_BWD=0 must reach the jnp _flash_block_grads fold
    while the FORWARD hops keep the kernel — and the two backward
    engines must agree on the gradients (the fold is the kernel path's
    parity oracle). hkv=2 exercises the per-hop GQA expand and the
    group-summed travelling accumulators."""
    context = pallas_interpret
    h, n, d = 4, 8 * 128, 128
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    p = sp_mesh.shape["sp"]

    want_stamp = ("pallas:b128:pf" if hkv == h
                  else "pallas:b128:kvx2:pf")
    assert context.ring_hop_bwd_engine_for(
        q, k, v, p=p, causal=True) == want_stamp

    def loss(q_, k_, v_):
        return jnp.sum(
            ring_attention(q_, k_, v_, mesh=sp_mesh, causal=True) ** 2)

    g_kernel = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    try:
        context._RING_HOP_BWD = False
        jax.clear_caches()
        assert context.ring_hop_bwd_engine_for(
            q, k, v, p=p, causal=True) == "jnp"
        assert context.ring_hop_engine_for(
            q, k, v, p=p, causal=True).startswith("pallas:")
        g_fold = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        context._RING_HOP_BWD = True
        jax.clear_caches()
    for name, a, b in zip("dq dk dv".split(), g_kernel, g_fold):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("hkv", [2, 1])
def test_ring_zigzag_hopflash_interpret_parity(rng, sp_mesh,
                                               pallas_interpret, hkv):
    """Causal zigzag with the per-hop Pallas engine engaged (interpret
    mode, 8-virtual-device mesh): the half-chunk kernel decomposition
    must match the dense oracle AND the jnp zigzag fold it replaced
    (MOMP_RING_ZZ=0), forward and grads — the grads additionally prove
    the lo‖hi (o, L) residual handoff to the zigzag jnp backward."""
    from mpi_and_open_mp_tpu.parallel.context import (
        zigzag_shard, zigzag_unshard)

    context = pallas_interpret
    h, d = 2, 128
    p = sp_mesh.shape["sp"]
    n = p * 256  # 256-token shards -> 128-token halves: interpret-eligible
    q = jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, n, d)), jnp.float32)

    stamp = context.ring_hop_engine_for(q, k, v, p=p, causal=True,
                                        layout="zigzag")
    assert stamp == ("pallas:b128:zz:pf" if hkv == h
                     else "pallas:b128:kvx2:zz:pf")
    # Zigzag gradients stay on the jnp fold — truthful provenance.
    assert context.ring_hop_bwd_engine_for(
        q, k, v, p=p, causal=True, layout="zigzag") == "jnp"

    qz, kz, vz = (zigzag_shard(x, p) for x in (q, k, v))
    got = zigzag_unshard(
        ring_attention(qz, kz, vz, mesh=sp_mesh, causal=True,
                       layout="zigzag"), p)
    kr = jnp.repeat(k, h // hkv, axis=0)
    vr = jnp.repeat(v, h // hkv, axis=0)
    want = attention_reference(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

    def loss(q_, k_, v_):
        return jnp.sum(ring_attention(q_, k_, v_, mesh=sp_mesh,
                                      causal=True, layout="zigzag") ** 2)

    g_kernel = jax.grad(loss, argnums=(0, 1, 2))(qz, kz, vz)
    # MOMP_RING_ZZ=0: the jnp zigzag fold, fwd + grads, must agree.
    try:
        context._RING_ZZ = False
        jax.clear_caches()
        assert context.ring_hop_engine_for(
            q, k, v, p=p, causal=True, layout="zigzag") == "jnp"
        fold = zigzag_unshard(
            ring_attention(qz, kz, vz, mesh=sp_mesh, causal=True,
                           layout="zigzag"), p)
        g_fold = jax.grad(loss, argnums=(0, 1, 2))(qz, kz, vz)
    finally:
        context._RING_ZZ = True
        jax.clear_caches()
    np.testing.assert_allclose(np.asarray(got), np.asarray(fold),
                               rtol=1e-4, atol=1e-4)
    for name, a, b in zip("dq dk dv".split(), g_kernel, g_fold):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3, err_msg=name)


def test_ring_hop_engines_chaos_recovery_interplay(rng, sp_mesh,
                                                   pallas_interpret,
                                                   monkeypatch):
    """Chaos-recovery interplay with BOTH hop engines engaged: a
    NaN-poisoned kernel hop must re-dispatch onto the full jnp fold
    oracle (the _ring_hop_pinned(False) recovery trace pins forward AND
    backward kernels off), land finite with oracle parity, and record
    the ``:recovered`` stamp."""
    from mpi_and_open_mp_tpu.robust import chaos, guards

    context = pallas_interpret
    h, n, d = 2, 8 * 128, 128
    q, k, v = (jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
               for _ in range(3))
    stamp = context.ring_hop_engine_for(
        q, k, v, p=sp_mesh.shape["sp"], causal=True)
    # The poisoned hop is the PREFETCHED one (:pf): recovery must pin
    # the double-slot schedule off along with both kernels.
    assert stamp.startswith("pallas:") and stamp.endswith(":pf")

    monkeypatch.setenv("MOMP_CHAOS", "nan_hop=2;seed=7")
    chaos.reset()
    guards.clear_recovery_log()
    try:
        out = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    finally:
        monkeypatch.delenv("MOMP_CHAOS")
        chaos.reset()
        jax.clear_caches()
    assert np.isfinite(np.asarray(out)).all()
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert any(s.startswith("ring_attention:jnp:recovered")
               for s in guards.recovery_log())
