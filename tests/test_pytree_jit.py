"""DNDarray pytree protocol: whole ``ht.*`` pipelines under ``jax.jit``/
``jax.grad`` (beyond the reference, whose torch+mpi4py model is eager-only —
reference heat/core/dndarray.py has no compiled-pipeline story).

The registration contract (dndarray.py:_tree_flatten): the leaf is the
PHYSICAL payload, aux is static (gshape, dtype, split, device, comm). Every
eager op costs one host dispatch, so "jit the pipeline" is the product answer
to dispatch-bound chains.

vmap/scan over DNDarray leaves is intentionally unsupported: shape-changing
transforms would desynchronize the static gshape from the payload; use
``.larray`` inside those transforms.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import heat_tpu as ht
from heat_tpu.core.dndarray import DNDarray

class TestPytreeProtocol:
    def test_flatten_unflatten_roundtrip_even(self):
        x = ht.arange(40, dtype=ht.float32, split=0)
        leaves, treedef = jax.tree_util.tree_flatten(x)
        assert len(leaves) == 1 and isinstance(leaves[0], jax.Array)
        y = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(y, DNDarray)
        assert y.shape == x.shape and y.split == x.split and y.dtype == x.dtype
        assert y.comm is x.comm and y.device is x.device
        np.testing.assert_array_equal(y.numpy(), x.numpy())

    def test_flatten_carries_physical_payload_when_padded(self):
        x = ht.arange(37, dtype=ht.float32, split=0)  # ragged over the mesh
        (payload,), treedef = jax.tree_util.tree_flatten(x)
        assert tuple(payload.shape) == tuple(x.parray.shape)
        y = jax.tree_util.tree_unflatten(treedef, (payload,))
        assert y.shape == (37,) and y.padded == x.padded
        np.testing.assert_array_equal(y.numpy(), np.arange(37, dtype=np.float32))

    def test_tree_map_identity_preserves_metadata(self):
        x = ht.ones((6, 5), dtype=ht.int32, split=1)
        y = jax.tree_util.tree_map(lambda a: a, x)
        assert isinstance(y, DNDarray)
        assert y.shape == (6, 5) and y.split == 1 and y.dtype == ht.int32

    def test_block_until_ready_descends(self):
        x = ht.ones(16, split=0)
        jax.block_until_ready(x)  # must not raise; payload is the leaf


class TestJitPipelines:
    def test_jit_pipeline_matches_eager_traced_once(self):
        x = ht.arange(37, dtype=ht.float32, split=0)  # ragged
        y = ht.full(37, 2.0, dtype=ht.float32, split=0)
        calls = {"n": 0}

        def pipe(a, b):
            calls["n"] += 1
            c = a * 2.0 + b
            d = ht.exp(-c / 40.0)
            return ht.mean(d * c), ht.sum(c)

        jp = jax.jit(pipe)
        m1, s1 = jp(x, y)
        m2, s2 = jp(x, y)
        assert calls["n"] == 1  # second call hit the jit cache
        me, se = pipe(x, y)
        assert isinstance(m1, DNDarray) and m1.shape == ()
        assert np.isclose(float(m1.larray), float(me.larray))
        assert np.isclose(float(s1.larray), float(se.larray))
        assert np.isclose(float(m2.larray), float(me.larray))

    def test_jit_mixed_split_operands(self):
        a = ht.arange(24, dtype=ht.float32, split=0).reshape((6, 4))
        b = ht.ones((6, 4), dtype=ht.float32)  # replicated

        out = jax.jit(lambda u, v: u + v * 3.0)(a, b)
        assert isinstance(out, DNDarray)
        np.testing.assert_array_equal(
            out.numpy(), np.arange(24, dtype=np.float32).reshape(6, 4) + 3.0
        )

    def test_jit_matmul_reduction_pipeline(self):
        rng = np.random.default_rng(3)
        an = rng.standard_normal((16, 8)).astype(np.float32)
        bn = rng.standard_normal((8, 12)).astype(np.float32)
        a = ht.array(an, split=0)
        b = ht.array(bn)

        def f(u, v):
            return ht.sum(ht.linalg.matmul(u, v), axis=1)

        out = jax.jit(f)(a, b)
        assert isinstance(out, DNDarray) and out.shape == (16,)
        np.testing.assert_allclose(out.numpy(), (an @ bn).sum(axis=1), rtol=2e-5)

    def test_jit_output_split_metadata(self):
        x = ht.arange(32, dtype=ht.float32, split=0)
        out = jax.jit(lambda a: a * a)(x)
        assert out.split == 0 and out.shape == (32,)
        # the compiled output still carries the split-axis sharding
        assert len(set(s.device for s in out.parray.addressable_shards)) == len(
            jax.devices()
        )


class TestGradThroughHtOps:
    def test_grad_returns_dndarray_with_metadata(self):
        x = ht.arange(37, dtype=ht.float32, split=0)
        g = jax.grad(lambda a: ht.mean(a * a).larray)(x)
        assert isinstance(g, DNDarray)
        assert g.shape == (37,) and g.split == 0
        np.testing.assert_allclose(
            g.numpy(), 2.0 / 37.0 * np.arange(37, dtype=np.float32), rtol=1e-6
        )

    def test_value_and_grad_pipeline(self):
        rng = np.random.default_rng(7)
        wn = rng.standard_normal((5, 3)).astype(np.float32)
        xn = rng.standard_normal((20, 5)).astype(np.float32)
        w = ht.array(wn)
        x = ht.array(xn, split=0)

        def loss(wv):
            pred = ht.linalg.matmul(x, wv)
            return ht.mean(pred * pred).larray

        val, grad = jax.value_and_grad(loss)(w)
        # numpy oracle
        pn = xn @ wn
        np.testing.assert_allclose(float(val), (pn * pn).mean(), rtol=2e-5)
        gn = 2.0 * xn.T @ pn / pn.size
        np.testing.assert_allclose(grad.numpy(), gn, rtol=2e-4, atol=1e-5)


class TestOpTraceability:
    """The op library composes under jit: ops whose host reads were
    incidental (histc's data-derived range, trace's scalar read, det's
    singular-tile probe, cholesky's LinAlgError probe) now defer them under
    a trace; inherently data-dependent ops (unique/nonzero: output shapes;
    allclose: Python bool) raise jax's standard concretization errors."""

    def test_histc_traces_and_matches_eager(self):
        v = ht.arange(16, dtype=ht.float32, split=0)
        j = jax.jit(lambda a: ht.histc(a, bins=4))(v)
        e = ht.histc(v, bins=4)
        np.testing.assert_array_equal(j.numpy(), e.numpy())

    def test_trace_traces_returns_0d(self):
        sq = ht.array(np.eye(4, dtype=np.float32) * 3 + 1, split=0)
        j = jax.jit(lambda a: ht.trace(a))(sq)
        assert isinstance(j, DNDarray) and j.shape == ()
        assert float(j.larray) == ht.trace(sq)  # eager keeps the scalar contract

    def test_det_then_slogdet_under_jit_no_tracer_leak(self):
        # the cached program factories must not bake trace-time constants:
        # det's first run under an outer jit used to poison the lru_cache
        # for every later slogdet/solve trace
        sq = ht.array(np.eye(4, dtype=np.float32) * 3 + 1, split=0)
        d = jax.jit(lambda a: ht.linalg.det(a))(sq)
        s = jax.jit(lambda a: ht.linalg.slogdet(a)[1])(sq)
        np.testing.assert_allclose(float(d.larray), 189.0, rtol=1e-5)
        np.testing.assert_allclose(float(s.larray), np.log(189.0), rtol=1e-5)

    def test_solve_triangular_and_cholesky_under_jit(self):
        rng = np.random.default_rng(0)
        Ln = np.tril(rng.standard_normal((8, 8)).astype(np.float32)) + 4 * np.eye(
            8, dtype=np.float32
        )
        bn = rng.standard_normal((8, 2)).astype(np.float32)
        L = ht.array(Ln, split=0)
        b = ht.array(bn, split=0)
        xj = jax.jit(lambda A, r: ht.linalg.solve_triangular(A, r, lower=True))(L, b)
        np.testing.assert_allclose(xj.numpy(), np.linalg.solve(Ln, bn), rtol=2e-5, atol=1e-6)
        cj = jax.jit(lambda A: ht.linalg.cholesky(ht.linalg.matmul(A, A.T)))(L)
        np.testing.assert_allclose(cj.numpy(), np.linalg.cholesky(Ln @ Ln.T), rtol=2e-4, atol=1e-4)
        # the eager LinAlgError contract survives the trace-aware guard
        with pytest.raises(np.linalg.LinAlgError):
            ht.linalg.cholesky(ht.array(-np.eye(4, dtype=np.float32), split=0))

    def test_untraceable_ops_raise_standard_errors(self):
        v = ht.arange(16, dtype=ht.float32, split=0)
        for fn in (
            lambda a: ht.unique(a),
            lambda a: ht.nonzero(a),
            lambda a: ht.allclose(a, a),
        ):
            with pytest.raises(Exception) as ei:
                jax.jit(fn)(v)
            assert "Tracer" in repr(ei.value) or "Concretization" in repr(ei.value)


class TestCheckpointInterplay:
    def test_checkpoint_tree_with_dndarray(self, tmp_path):
        from heat_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

        x = ht.arange(37, dtype=ht.float32, split=0)  # ragged: padded payload
        tree = {"w": x, "step": jnp.asarray(3)}
        save_checkpoint(str(tmp_path), tree, step=0)
        restored = load_checkpoint(str(tmp_path), {"w": np.zeros(37, np.float32), "step": 0})
        # the LOGICAL array was serialized — not the padded physical payload
        np.testing.assert_array_equal(restored["w"], np.arange(37, dtype=np.float32))
