"""Hand-tiled pallas flash-attention kernel for TPU.

The scan-based :func:`heat_tpu.nn.attention.flash_attention` leaves the tile
schedule to XLA; this kernel owns it: the FULL (batch·head, q_block, k_block)
tiling lives on the pallas grid — K/V tiles are streamed HBM→VMEM one
(block_k, D) block per grid step by BlockSpecs (so pallas double-buffers the
fetch against the previous tile's compute, and sequence length is NOT capped
by VMEM), the two matmuls per tile hit the MXU, and the online-softmax state
(m, l, acc) lives in VMEM scratch that carries across the k-axis grid steps.
With ``causal=True`` tiles strictly above the diagonal skip their compute
via ``pl.when`` AND their K/V copies via a clamped (repeating) block index —
half the FLOPs and half the K/V traffic at a uniform grid.

The reference framework has no attention; this kernel is the long-context
hot-op analog of its densest compute path (the cdist tile kernel,
reference spatial/distance.py:16-134 → heat_tpu/ops/pairwise.py).

Layout: heads fold into the grid's leading axis ([B, H] → programs), head_dim
is the lane axis padded to 128, sequence is the sublane axis in (block, D)
tiles. VMEM holds one Q tile, one K/V tile pair (double-buffered), the
(block_q, D) f32 accumulator and two (block_q, 128) state columns — a few
hundred KB regardless of S.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_tpu", "pallas_attention_supported"]

_LANE = 128
_NEG_INF = -1e30  # large-negative instead of -inf: exp() underflows to 0 identically


def pallas_attention_supported(seq_len: int, head_dim: int) -> bool:
    """TPU backend present and the head fits the lane tile. K/V stream per
    block, so sequence length does not cap the kernel."""
    return jax.default_backend() == "tpu" and head_dim <= 4 * _LANE


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale, causal, block_q, block_k, sk, nk,
):
    """One (batch·head, q-block, k-block) grid step: fold one K/V tile into
    the online-softmax scratch state; finalize into ``o_ref`` on the last
    k-step. The k axis is the FASTEST grid dimension, so the scratch
    (m, l, acc) carries one q-block's state across its k sweep.

    bfloat16 inputs stay bfloat16 on both MXU contractions (scores and
    values, ``preferred_element_type=f32``) — casting to f32 would halve the
    MXU rate; the state is always f32. The scale is folded into the q tile
    once per k-step (cheap: (block_q, D) vs the (block_q, block_k) score).

    The state is kept 2-D with a 128-lane minor axis ((block_q, LANE), not
    (block_q,)): Mosaic lays 1-D vectors out with a replicated sublane, and
    chaining max / exp / where through that layout costs a relayout per
    k-tile — the same layout class that broke the Lloyd kernel outright
    (ops/lloyd.py). keepdims everywhere keeps the loop relayout-free."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    q_idx0 = iq * block_q
    k0 = jk * block_k

    @pl.when(jk == 0)
    def _init():
        m_ref[:, :] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:, :] = jnp.zeros_like(l_ref)
        acc_ref[:, :] = jnp.zeros_like(acc_ref)

    # causal: tiles strictly above the diagonal contribute nothing — skip
    # their compute (the fetch is pipelined regardless; FLOPs halve)
    live = True
    if causal:
        live = k0 <= q_idx0 + (block_q - 1)

    @pl.when(live)
    def _tile():
        mm_dtype = q_ref.dtype if q_ref.dtype == jnp.bfloat16 else jnp.float32
        q = (q_ref[0].astype(jnp.float32) * scale).astype(mm_dtype)  # (block_q, D)
        kb = k_ref[0].astype(mm_dtype)  # (block_k, D)
        vb = v_ref[0].astype(mm_dtype)
        m = m_ref[:, :1]  # (block_q, 1) view of the state column
        l = l_ref[:, :1]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k); scale pre-folded into q
        k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        keep = k_ids < sk  # mask sequence padding
        if causal:
            q_ids = q_idx0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            keep = keep & (q_ids >= k_ids)
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        # rows with m_new == _NEG_INF are all-masked; zero their probabilities
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m - m_new)  # (block_q, 1)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:, :] = alpha * acc_ref[:, :] + jax.lax.dot_general(
            p.astype(mm_dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, :] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:, :] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:, :] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention_tpu(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Pallas flash attention on [B, S, H, D] inputs (same contract as
    :func:`heat_tpu.nn.attention.flash_attention`).

    Default tiles are (256, 512): 128-wide MXU contractions are too small
    to amortize the per-tile softmax state updates; larger tiles raise
    arithmetic intensity per k-axis grid step."""
    B, S, H, D = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    d_pad = max(_LANE, -(-D // _LANE) * _LANE)
    sq_pad = -(-S // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k

    def to_bhsd(x, s_pad):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, x.shape[1], D)
        return jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, d_pad - D)))

    qf, kf, vf = to_bhsd(q, sq_pad), to_bhsd(k, sk_pad), to_bhsd(v, sk_pad)
    nq, nk = sq_pad // block_q, sk_pad // block_k

    if causal:
        # above-diagonal k-steps are compute-skipped by pl.when; clamping
        # their block index to the q-block's LAST live tile makes the index
        # repeat, and pallas skips the copy for a repeated index — so dead
        # steps move no HBM bytes either (the old fori_loop design's
        # never-read-above-diagonal guarantee, kept on the uniform grid)
        def kv_index(bh, iq, jk):
            last_live = (iq * block_q + (block_q - 1)) // block_k
            return (bh, jnp.minimum(jk, last_live), 0)

    else:
        def kv_index(bh, iq, jk):
            return (bh, jk, 0)

    out = pl.pallas_call(
        functools.partial(
            _attn_kernel,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            sk=sk,
            nk=nk,
        ),
        # k is the FASTEST axis: each q-block's online-softmax state carries
        # across its k sweep in VMEM scratch; pallas streams one K/V tile
        # per step (double-buffered against the previous tile's matmuls)
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda bh, iq, jk: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d_pad), kv_index),
            pl.BlockSpec((1, block_k, d_pad), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda bh, iq, jk: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, sq_pad, d_pad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # m (col 0 live)
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # l
            pltpu.VMEM((block_q, d_pad), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :S, :D].reshape(B, H, S, D)
    return jnp.transpose(out, (0, 2, 1, 3))
