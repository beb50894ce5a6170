"""Sequence-parallel attention: dense, blockwise (flash-style), ring, Ulysses.

The reference framework has no attention/sequence dimension (SURVEY.md §2.3:
TP/PP/EP/Ulysses "absent"), but it owns the *mechanisms* long-context
attention is made of: the systolic ring of ``spatial/distance.py:265-369``
(rotate the moving operand with Send-to-(rank+i), compute one tile per step)
and the Alltoall axis re-sharding of ``manipulations.py:3329-3425``. This
module makes those mechanisms first-class for the long-context case:

* :func:`dot_product_attention` — dense softmax attention, the oracle.
* :func:`flash_attention` — blockwise online-softmax attention expressed as a
  ``lax.scan`` over key/value tiles. O(seq) memory instead of O(seq²); XLA
  fuses each tile into MXU matmuls.
* :func:`ring_attention` — sequence parallelism over the device mesh: Q stays
  resident, K/V shards rotate via ``lax.ppermute`` (exactly the reference's
  ring cdist schedule), each step folding one tile into the online-softmax
  accumulator. Communication rides ICI; memory per chip is O(seq/p).
* :func:`ulysses_attention` — all-to-all sequence parallelism: ``lax.
  all_to_all`` re-shards [B, S/p, H, D] → [B, S, H/p, D], runs dense/blockwise
  attention per local head group, and re-shards back (the Ulysses layout
  switch; the reference's analogous axis-changing resplit is
  communication.py:336-437).

All functions take [batch, seq, heads, head_dim] arrays (flax convention) and
accumulate the softmax in float32 regardless of input dtype (bfloat16 inputs
stay bfloat16 on the matmul operands — MXU-friendly — while m/l/o run f32).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.communication import MeshCommunication, sanitize_comm

__all__ = [
    "dot_product_attention",
    "flash_attention",
    "ring_attention",
    "ulysses_attention",
    "MultiHeadAttention",
]


def _acc_dtype(dtype) -> jnp.dtype:
    """float32 accumulation, widened to f64 only if the inputs already are."""
    return jnp.promote_types(dtype, jnp.float32)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Dense softmax attention (the oracle the parallel paths are tested against).

    Parameters
    ----------
    q, k, v : jax.Array
        [batch, seq, heads, head_dim] (k/v may have a different seq length).
    causal : bool
        Lower-triangular masking (query i attends to keys ≤ i).
    scale : float, optional
        Score scale; default ``1/sqrt(head_dim)``.
    mask : jax.Array, optional
        Boolean, broadcastable to [batch, q_len, heads, k_len]; True = keep.
    """
    acc = _acc_dtype(q.dtype)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k).astype(acc) * scale
    if causal:
        q_ids = jnp.arange(q.shape[1])
        k_ids = jnp.arange(k.shape[1])
        cm = (q_ids[:, None] >= k_ids[None, :])[None, :, None, :]
        s = jnp.where(cm, s, -jnp.inf)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", p.astype(v.dtype), v)


def _tile_update(q, k_blk, v_blk, m, l, o, q_idx0, k_idx0, causal, scale, kv_valid=None):
    """Fold one K/V tile into the online-softmax state (m, l, o).

    m: [B, sq, H] running max (f32); l: [B, sq, H] running sum; o: [B, sq, H, D]
    unnormalized output. q_idx0/k_idx0 are the global sequence offsets of the
    tiles, so causal masking is correct regardless of which shard is visiting.
    ``kv_valid`` (optional, [bk] bool) masks out padded key positions.
    """
    acc = m.dtype
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k_blk).astype(acc) * scale
    k_ids = k_idx0 + jnp.arange(k_blk.shape[1])
    keep = None
    if causal:
        q_ids = q_idx0 + jnp.arange(q.shape[1])
        keep = (q_ids[:, None] >= k_ids[None, :])[None, :, None, :]
    if kv_valid is not None:
        kv = kv_valid[None, None, None, :]
        keep = kv if keep is None else keep & kv
    if keep is not None:
        s = jnp.where(keep, s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # A fully-masked history has m_new = -inf; shift by 0 there so exp() is 0,
    # not NaN (the final division is guarded the same way).
    m_safe = jnp.where(jnp.isneginf(m_new), jnp.zeros((), acc), m_new)
    p = jnp.exp(s - m_safe[..., None])
    alpha = jnp.exp(m - m_safe)  # m = -inf -> 0: no prior mass
    l_new = alpha * l + p.sum(axis=-1)
    o_new = alpha[..., None] * o + jnp.einsum("bqhk,bkhd->bqhd", p, v_blk.astype(acc))
    return m_new, l_new, o_new


def _finalize(l, o, dtype):
    denom = jnp.where(l > 0, l, jnp.ones((), l.dtype))
    return (o / denom[..., None]).astype(dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_size: int = 512,
) -> jax.Array:
    """Blockwise online-softmax attention (flash-style): a ``lax.scan`` over
    key tiles of ``block_size``; runs everywhere, fully differentiable, XLA
    schedules the tiles.

    Memory is O(q_len·heads·head_dim) instead of O(q_len·k_len·heads).
    """
    acc = _acc_dtype(q.dtype)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    B, sq, H, D = q.shape
    sk = k.shape[1]
    bk = min(block_size, sk)
    nb = -(-sk // bk)
    pad = nb * bk - sk
    if pad:
        # padded keys are masked out via the causal/index mask below
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ks = k.reshape(B, nb, bk, H, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nb, bk, H, D).transpose(1, 0, 2, 3, 4)

    # seed the carry from q so it has q's varying-axes type under shard_map
    # (a replicated zero carry would mismatch the varying scan outputs)
    zero = (q[(0,) * q.ndim] * 0).astype(acc)
    m0 = jnp.full((B, sq, H), -jnp.inf, acc) + zero
    l0 = jnp.zeros((B, sq, H), acc) + zero
    o0 = jnp.zeros((B, sq, H, D), acc) + zero

    def step(carry, blk):
        i, m, l, o = carry
        k_blk, v_blk = blk
        k_idx0 = i * bk
        kv_valid = k_idx0 + jnp.arange(bk) < sk
        m, l, o = _tile_update(q, k_blk, v_blk, m, l, o, 0, k_idx0, causal, scale, kv_valid)
        return (i + 1, m, l, o), None

    (_, _, l, o), _ = jax.lax.scan(step, (jnp.zeros((), jnp.int32), m0, l0, o0), (ks, vs))
    return _finalize(l, o, q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    comm: Optional[MeshCommunication] = None,
) -> jax.Array:
    """Ring-parallel attention over the mesh's sequence axis.

    Q/K/V enter sharded [B, S, H, D] with S block-distributed over the mesh
    (``split=1`` in framework terms). Each device keeps its Q shard resident
    while K/V shards rotate around the ring via ``lax.ppermute`` — the exact
    communication schedule of the reference's systolic cdist
    (spatial/distance.py:272-327) — folding one tile per step into the
    online-softmax state. Per-chip memory is O(S/p); the p-1 permutes ride ICI
    and overlap with the tile matmuls under XLA's latency-hiding scheduler.
    """
    comm = sanitize_comm(comm)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    S = q.shape[1]
    if S % comm.size:
        raise ValueError(f"ring_attention requires seq {S} divisible by mesh size {comm.size}")
    fn = _ring_attention_fn(comm.mesh, comm.axis_name, bool(causal), float(scale))
    return fn(q, k, v)


@functools.lru_cache(maxsize=None)
def _ring_attention_fn(mesh, axis, causal, scale):
    """Jitted shard_map ring kernel, cached per (mesh, causal, scale) so eager
    callers reuse XLA's compile cache instead of retracing a fresh closure."""
    p_sz = mesh.shape[axis]

    def kernel(ql, kl, vl):
        acc = _acc_dtype(ql.dtype)
        rank = jax.lax.axis_index(axis)
        B, sq, H, D = ql.shape
        q_idx0 = rank * sq
        m0 = jnp.full((B, sq, H), -jnp.inf, acc)
        l0 = jnp.zeros((B, sq, H), acc)
        o0 = jnp.zeros((B, sq, H, D), acc)
        try:  # constants start replicated; mark them varying for the carry
            m0, l0, o0 = (jax.lax.pcast(x, (axis,), to="varying") for x in (m0, l0, o0))
        except (AttributeError, TypeError):  # pragma: no cover - older jax
            pass

        def fold(i, kc, vc, m, l, o):
            # kc/vc currently hold the shard owned by device (rank + i) % p
            k_idx0 = ((rank + i.astype(rank.dtype)) % p_sz) * sq
            return _tile_update(ql, kc, vc, m, l, o, q_idx0, k_idx0, causal, scale)

        def body(i, carry):
            kc, vc, m, l, o = carry
            m, l, o = fold(i, kc, vc, m, l, o)
            perm = [(j, (j - 1) % p_sz) for j in range(p_sz)]
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return kc, vc, m, l, o

        # p-1 rotations: the loop body permutes after each fold; the last
        # shard is folded outside so its rotation is never issued.
        kl, vl, m, l, o = jax.lax.fori_loop(0, p_sz - 1, body, (kl, vl, m0, l0, o0))
        m, l, o = fold(jnp.asarray(p_sz - 1), kl, vl, m, l, o)
        return _finalize(l, o, ql.dtype)

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(None, axis), P(None, axis), P(None, axis)),
            out_specs=P(None, axis),
        )
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    comm: Optional[MeshCommunication] = None,
    block_size: int = 0,
) -> jax.Array:
    """All-to-all (Ulysses) sequence-parallel attention.

    Two ``lax.all_to_all`` layout switches re-shard [B, S/p, H, D] →
    [B, S, H/p, D] (sequence-sharded → head-sharded), run full-sequence
    attention on each device's head group, and switch back — the attention
    instance of the reference's axis-changing resplit (Alltoallw,
    communication.py:336-437). Requires ``heads % p == 0``. With
    ``block_size > 0`` the local attention is the blockwise
    :func:`flash_attention` (O(S) memory); otherwise dense.
    """
    comm = sanitize_comm(comm)
    p_sz = comm.size
    H = q.shape[2]
    if H % p_sz:
        raise ValueError(f"ulysses_attention requires heads {H} divisible by mesh size {p_sz}")
    if q.shape[1] % p_sz:
        raise ValueError(f"seq {q.shape[1]} not divisible by mesh size {p_sz}")
    scale_f = float(scale) if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    fn = _ulysses_attention_fn(comm.mesh, comm.axis_name, bool(causal), scale_f, int(block_size))
    return fn(q, k, v)


@functools.lru_cache(maxsize=None)
def _ulysses_attention_fn(mesh, axis, causal, scale, block_size):
    """Jitted shard_map Ulysses kernel, cached per configuration (see
    :func:`_ring_attention_fn` for why)."""
    local = (
        functools.partial(flash_attention, block_size=block_size)
        if block_size
        else dot_product_attention
    )

    def kernel(ql, kl, vl):
        # [B, S/p, H, D] -> [B, S, H/p, D]: split heads, gather sequence
        qh, kh, vh = (
            jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)
            for x in (ql, kl, vl)
        )
        oh = local(qh, kh, vh, causal=causal, scale=scale)
        return jax.lax.all_to_all(oh, axis, split_axis=1, concat_axis=2, tiled=True)

    return jax.jit(
        jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(None, axis), P(None, axis), P(None, axis)),
            out_specs=P(None, axis),
        )
    )


_BACKENDS: dict = {}


def _resolve_backend(name: str) -> Callable:
    if not _BACKENDS:
        _BACKENDS.update(
            dense=dot_product_attention,
            flash=flash_attention,
            ring=ring_attention,
            ulysses=ulysses_attention,
        )
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown attention backend {name!r}; one of {sorted(_BACKENDS)}")


import flax.linen as fnn


class MultiHeadAttention(fnn.Module):
    """Multi-head self-attention with a pluggable sequence-parallel backend.

    ``backend`` selects among 'dense', 'flash', 'ring', 'ulysses'. The
    projections are ordinary Dense layers (sharded by GSPMD when the
    activations are); only the score/value contraction is parallel-aware.

    This intentionally shadows ``flax.linen.MultiHeadAttention`` in the
    ``heat_tpu.nn`` namespace (different signature: no bias/dropout/decode;
    the parallel backends take ``comm``).
    """

    num_heads: int
    qkv_features: Optional[int] = None
    causal: bool = False
    backend: str = "dense"
    dtype: Optional[jnp.dtype] = None
    # direct kernel injection, overriding ``backend``: a callable
    # (q, k, v, causal=...) -> out, e.g. functools.partial(ring_attention,
    # comm=comm). One hook owns the backend plumbing for every consumer
    # (TransformerBlock composes this module rather than re-implementing it).
    attention_fn: Optional[Callable] = None

    @fnn.compact
    def __call__(self, x, comm: Optional[MeshCommunication] = None):
        features = self.qkv_features or x.shape[-1]
        if features % self.num_heads:
            raise ValueError("qkv_features must be divisible by num_heads")
        head_dim = features // self.num_heads
        dense = functools.partial(fnn.DenseGeneral, dtype=self.dtype)
        qkv_shape = (self.num_heads, head_dim)
        q = dense(features=qkv_shape, name="query")(x)
        k = dense(features=qkv_shape, name="key")(x)
        v = dense(features=qkv_shape, name="value")(x)
        kwargs = {"causal": self.causal}
        if self.attention_fn is not None:
            attn = self.attention_fn  # comm, scale etc. bound by the caller
        else:
            attn = _resolve_backend(self.backend)
            if self.backend in ("ring", "ulysses"):
                kwargs["comm"] = comm
        o = attn(q, k, v, **kwargs)
        return fnn.DenseGeneral(
            features=x.shape[-1], axis=(-2, -1), dtype=self.dtype, name="out"
        )(o)
