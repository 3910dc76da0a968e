"""Pallas TPU kernel: decode attention fused with block-float KV-cache
decompression (the paper's "reconstructed data is consumed on-device"
pattern applied to inference).

Without fusion, serving from a compressed cache costs an extra HBM round
trip: dequantize (write bf16 KV) then attend (read it back). This kernel
streams int8 codes + per-(token, head) scales HBM->VMEM, dequantizes in
VMEM registers, and runs the online-softmax accumulation in one pass —
the KV HBM traffic is the *compressed* bytes (8.25 bits/value), which is
the whole point: decode attention is HBM-bandwidth-bound, so fixed-rate 8x
-> ~2x step-time headroom vs bf16 caches at long context.

Grid: (batch, seq_chunks); seq chunk 128 rows x head_dim lanes. Running
max / denominator / accumulator live in VMEM scratch across chunk steps;
the final chunk writes the normalized output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

SEQ_CHUNK = 128


def _kvc_kernel(len_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref, o_ref,
                m_ref, l_ref, acc_ref):
    s_idx = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (H, D)
    k = kc_ref[0].astype(jnp.float32) * ks_ref[0][:, :, None]  # (C, H, D)
    v = vc_ref[0].astype(jnp.float32) * vs_ref[0][:, :, None]
    scale = q.shape[-1] ** -0.5
    # per-head q.k as a VPU multiply + lane reduction: one query row per
    # head is no MXU shape, and the TPU lowering rejects this batched
    # einsum's dimension numbers
    logits = jnp.sum(k * q[None], axis=-1).T * scale  # (H, C)
    pos = s_idx * SEQ_CHUNK + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = pos <= len_ref[pl.program_id(0), 0]
    logits = jnp.where(mask, logits, -1e30)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # the mask multiply is a bitwise no-op for live lanes (exp of -1e30
    # minus a real max underflows to exactly 0) but forces a fully-masked
    # lane (index -1 = free slot) to p = 0 everywhere -> output exactly 0,
    # independent of whatever the recycled cache rows hold
    p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
    l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
    acc_new = acc_prev * alpha + jnp.sum(v * p.T[:, :, None], axis=0)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(s_idx == n_chunks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kvc_decode_attention(q: jax.Array, k_codes: jax.Array, k_scale: jax.Array,
                         v_codes: jax.Array, v_scale: jax.Array,
                         index: jax.Array, interpret: bool | None = None) -> jax.Array:
    """q: (B, H, D); codes: (B, S, H, D) int8; scales: (B, S, H) f32;
    index: () shared position or (B,) per-slot positions — each lane b
    attends to cache[0..index[b]] (continuous batching admits requests at
    any tick, so lanes sit at different positions; a lane with index -1
    masks everything). GQA repeat is done by the caller (ops.py). Returns
    (B, H, D) in q.dtype."""
    b, h, d = q.shape
    s = k_codes.shape[1]
    assert s % SEQ_CHUNK == 0, "pad cache length to SEQ_CHUNK (ops.py)"
    grid = (b, s // SEQ_CHUNK)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1, 1), (b, 1))
    return pl.pallas_call(
        _kvc_kernel,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        grid=grid,
        in_specs=[
            # every lane's position, whole in SMEM (read at program_id(0))
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, SEQ_CHUNK, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, SEQ_CHUNK, h), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, SEQ_CHUNK, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, SEQ_CHUNK, h), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
        name="kvc_decode_attention",
        interpret=default_interpret(interpret),
    )(idx, q, k_codes, k_scale, v_codes, v_scale)
