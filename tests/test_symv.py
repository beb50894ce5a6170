"""``ops/symv.py``: the product of a float32 matrix's mirrored upper block
triangle with a vector (Pallas, interpret mode on the CPU), against float64,
and the rule that decides which Lanczos product a matrix gets.

Compiled for a described v5e at the ``spectral_1c`` size: ``test_kmeans_f32.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

import heat_tpu as ht
from heat_tpu.core.linalg import solver
from heat_tpu.ops import symv


def mirrored(a, block):
    """float64 ``a`` with each lower block replaced by the mirror of its upper one."""
    m = np.array(a, np.float64)
    t = m.shape[0] // block
    for i in range(t):
        for j in range(i):
            m[i * block:(i + 1) * block, j * block:(j + 1) * block] = m[j * block:(j + 1) * block, i * block:(i + 1) * block].T
    return m


@pytest.mark.parametrize("n,block", [(128, 128), (256, 128), (512, 128)], ids=["T1", "T2", "T4"])
@pytest.mark.parametrize("symmetric", [False, True], ids=["random", "symmetric"])
def test_the_product_is_the_mirrored_upper_block_triangles(n, block, symmetric):
    """Each off-diagonal upper block gives its rows' and its columns' part of
    the product, a diagonal block its rows' only, and no lower block is read:
    on a random matrix the product is that of its mirrored upper block
    triangle, on a symmetric one ``a @ v``."""
    rng = np.random.default_rng(n + symmetric)
    a = rng.standard_normal((n, n)).astype(np.float32)
    if symmetric:
        a = ((a + a.T) / 2).astype(np.float32)
    v = rng.standard_normal(n).astype(np.float32)
    y = symv.upper_matvec(jnp.asarray(a), jnp.asarray(v), block=block, interpret=True)
    assert y.dtype == jnp.float32 and y.shape == (n,)
    got, want = np.asarray(y, np.float64), mirrored(a, block) @ v.astype(np.float64)
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
    if not symmetric and n > block:  # the lower blocks are not read: a different matrix than a
        assert np.abs(a.astype(np.float64) @ v - want).max() > 1e-2 * np.abs(want).max()


def _one_device():
    return SingleDeviceSharding(jax.devices()[0])


@pytest.mark.parametrize(
    "shape,dtype,takes",
    [((1024, 1024), jnp.float32, True), ((symv.BLOCK, symv.BLOCK), jnp.float32, True),
     ((1000, 1000), jnp.float32, False), ((1024, 512), jnp.float32, False), ((1024,), jnp.float32, False),
     ((1024, 1024), jnp.bfloat16, False), ((1024, 1024), jnp.float64, False)],
)
def test_which_matrices_take_the_upper_product_on_a_tpu(monkeypatch, shape, dtype, takes):
    """On a TPU (the backend the test pretends), one device, square, float32,
    a side that is a multiple of the block: those and only those."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert symv.takes(shape, dtype, _one_device()) is takes


def test_no_matrix_takes_it_off_a_tpu_or_over_several_devices(monkeypatch):
    shape = (4 * symv.BLOCK, 4 * symv.BLOCK)
    assert not symv.takes(shape, jnp.float32, _one_device())  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
    for spec in (PartitionSpec("x", None), PartitionSpec(None, None)):  # rows split, replicated
        assert len(jax.devices()) > 1 and not symv.takes(shape, jnp.float32, NamedSharding(mesh, spec))


@pytest.mark.parametrize("split", [0, None])
def test_lanczos_takes_the_full_product_over_several_devices(monkeypatch, split):
    """A DNDarray of the CPU mesh lies on every device, split or replicated:
    ``ht.lanczos`` runs the program with XLA's product over all of A."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    taken = []
    monkeypatch.setattr(solver, "_lanczos_upper", lambda *a: taken.append("upper"))
    fused = solver._lanczos_fused
    monkeypatch.setattr(solver, "_lanczos_fused", lambda *a: taken.append("full") or fused(*a))
    rng = np.random.default_rng(3)
    a = rng.standard_normal((symv.BLOCK, symv.BLOCK)).astype(np.float32)
    A = ht.array((a + a.T) / 2, split=split)
    assert not solver._reads_upper(A, jnp.float32)
    ht.lanczos(A, 4)
    assert taken == ["full"]
