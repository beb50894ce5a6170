"""Sequence-parallel attention: ring/Ulysses/flash vs the dense oracle.

The reference has no attention (SURVEY.md §2.3), but its ring-cdist schedule
(spatial/distance.py:272-327) and Alltoall resplit (communication.py:336-437)
are exactly the mechanisms these paths are built from — tested here the same
way the reference tests its distributed ops: against a local oracle, across
sharded inputs on the forced 8-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.nn.attention import (
    MultiHeadAttention,
    dot_product_attention,
    flash_attention,
    ring_attention,
    ulysses_attention,
)

COMM = None


def setup_module():
    global COMM
    COMM = ht.get_comm()


def _qkv(B=2, S=None, H=None, D=16, dtype=jnp.float32, seed=0):
    # default sequence/head extents scale with the mesh so the suite passes
    # at any HEAT_TPU_TEST_DEVICES (the reference's tests branch on comm.size
    # the same way, e.g. reference test_communication.py ragged cases)
    if S is None:
        S = 8 * COMM.size
    if H is None:
        H = 2 * COMM.size
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, S, H, D)
    q = jax.random.normal(ks[0], shape, dtype)
    k = jax.random.normal(ks[1], shape, dtype)
    v = jax.random.normal(ks[2], shape, dtype)
    return q, k, v


def _shard_seq(x, comm):
    return jax.device_put(x, comm.sharding(x.ndim, 1))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_ragged_blocks():
    # seq length not divisible by block_size exercises the pad+mask tail
    q, k, v = _qkv(S=40)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    qs, ks, vs = (_shard_seq(x, COMM) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, causal=causal, comm=COMM)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    qs, ks, vs = (_shard_seq(x, COMM) for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, causal=causal, comm=COMM)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ulysses_blockwise_local():
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v)
    out = ulysses_attention(*( _shard_seq(x, COMM) for x in (q, k, v)), comm=COMM, block_size=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_bf16_inputs_f32_accumulation():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = dot_product_attention(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    out = ring_attention(*(_shard_seq(x, COMM) for x in (q, k, v)), comm=COMM)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
    )


def test_ring_gradients_match_dense():
    q, k, v = _qkv(B=1, S=4 * COMM.size, H=2, D=8)

    def loss_dense(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, causal=True, comm=COMM) ** 2).sum()

    g_ref = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    qs, ks, vs = (_shard_seq(x, COMM) for x in (q, k, v))
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(qs, ks, vs)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_ring_rejects_indivisible_seq():
    if COMM.size == 1:
        pytest.skip("every length divides a 1-device mesh")
    q, k, v = _qkv(S=8 * COMM.size + 1)
    with pytest.raises(ValueError):
        ring_attention(q, k, v, comm=COMM)


def test_ulysses_rejects_indivisible_heads():
    if COMM.size == 1:
        pytest.skip("every head count divides a 1-device mesh")
    q, k, v = _qkv(H=COMM.size + 1)
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, comm=COMM)


@pytest.mark.parametrize("backend", ["dense", "flash", "ring", "ulysses"])
def test_mha_module_backends_agree(backend):
    heads = 2 * COMM.size  # divisible for ulysses at any mesh size
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8 * COMM.size, 4 * heads))
    mod = MultiHeadAttention(num_heads=heads, causal=True, backend=backend)
    kwargs = {"comm": COMM} if backend in ("ring", "ulysses") else {}
    variables = MultiHeadAttention(num_heads=heads, causal=True, backend="dense").init(
        jax.random.PRNGKey(0), x
    )
    ref = MultiHeadAttention(num_heads=heads, causal=True, backend="dense").apply(variables, x)
    if backend in ("ring", "ulysses"):
        x_in = jax.device_put(x, COMM.sharding(3, 1))
    else:
        x_in = x
    out = mod.apply(variables, x_in, **kwargs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_long_sequence_ring_memory_shape():
    # a long-context smoke: S = 1024 over 8 devices -> 128 per chip
    if 1024 % COMM.size:
        pytest.skip("mesh size must divide 1024 for this smoke")
    q, k, v = _qkv(B=1, S=1024, H=4, D=8)
    qs, ks, vs = (_shard_seq(x, COMM) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, causal=True, comm=COMM)
    assert out.shape == (1, 1024, 4, 8)
    shard_rows = {s.data.shape[1] for s in out.addressable_shards}
    assert shard_rows == {1024 // COMM.size}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S,D", [(256, 16), (200, 16), (256, 128), (130, 8)])
def test_flash_tiles_of_128_match_dense(causal, S, D):
    # tiles of 128 keys: ragged S (a masked tail tile), D below and at the
    # 128 lanes, and several tiles folded into the online-softmax state
    q, k, v = _qkv(B=1, S=S, H=2, D=D, seed=5)
    out = flash_attention(q, k, v, causal=causal, block_size=128)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_bf16_stays_bf16():
    # bfloat16 inputs stay bfloat16 on both contractions and in the result;
    # only the online-softmax state is float32 — within bfloat16 rounding of
    # the float32 dense oracle
    q, k, v = _qkv(B=1, S=256, H=2, D=64, seed=7)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_size=128)
    assert out.dtype == jnp.bfloat16
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.05
    )


def test_flash_block_larger_than_the_sequence():
    # one tile of the whole sequence: block_size clips to the key length
    q, k, v = _qkv(B=1, S=96, H=2, D=16, seed=6)
    out = flash_attention(q, k, v, causal=True, block_size=512)
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_cross_attention_lengths():
    # 70 queries against 300 keys: the key axis alone is tiled and padded
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 70, 2, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 300, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 300, 2, 16), jnp.float32)
    out = flash_attention(q, k, v, block_size=128)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_flash_grads_match_dense():
    # the scan differentiates as it runs: its gradients are the dense oracle's
    q, k, v = _qkv(B=1, S=32, H=2, D=8)

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v, causal=True) ** 2).sum()

    g_ref = jax.grad(loss(dot_product_attention), argnums=(0, 1, 2))(q, k, v)
    flash = lambda q, k, v, causal: flash_attention(q, k, v, causal=causal, block_size=8)  # noqa: E731
    g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
