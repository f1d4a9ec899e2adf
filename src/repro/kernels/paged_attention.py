"""Pallas TPU kernel: flash-decode attention over a K-way-managed paged KV.

The serving-side consumer of the paper's cache: KV pages live in a dense
page pool; the K-way set-associative page table (core/kway.py) decides which
pages are resident.  This kernel computes one decode step of GQA attention
for a batch of sequences whose KV is scattered across pages.

TPU design (vLLM's paged attention re-thought for the TPU pipeline):
  * Grid = (batch, kv_heads, pages_per_seq); the page axis is innermost and
    sequential, so the online-softmax accumulators live in VMEM scratch and
    survive across page steps (flash-decode).
  * The page indirection is resolved by the BlockSpec ``index_map`` reading
    the page table from **scalar prefetch** — the DMA engine fetches page
    ``page_table[b, p]`` HBM→VMEM while the previous page is being consumed.
    This is the TPU-native replacement for the GPU's gather warp: the
    indirection costs nothing on the compute path.
  * Each grid step does one [G, D] x [D, page] MXU matmul (G = q heads per
    kv head) + a VPU online-softmax update — no materialized [B, T] logits.

Numerics: accumulation in f32; masked lanes excluded via explicit where
(never exp(-inf - -inf)); empty sequences (seq_len == 0) produce zeros.

Oracle: ref.paged_attention_ref.  Sweeps in tests/test_paged_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -3.0e38


def _decode_kernel(
    # scalar prefetch
    page_table_ref,   # int32 [B, PPS]
    seq_lens_ref,     # int32 [B]
    # VMEM in
    q_ref,            # [1, 1, G, D]
    k_ref,            # [1, 1, page, D]
    v_ref,            # [1, 1, page, D]
    # VMEM out
    o_ref,            # [1, 1, G, D]
    # scratch
    m_ref,            # f32 [G, 1]
    l_ref,            # f32 [G, 1]
    acc_ref,          # f32 [G, D]
    *,
    scale: float,
    softcap: float,
    page: int,
    pps: int,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = seq_lens_ref[b]
    q = q_ref[0, 0].astype(jnp.float32)        # [G, D]
    k = k_ref[0, 0].astype(jnp.float32)        # [page, D]
    v = v_ref[0, 0].astype(jnp.float32)        # [page, D]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # [G, page]
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap

    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < seq_len                       # [1, page]
    logits = jnp.where(valid, logits, NEG_INF)

    m_prev = m_ref[...]                         # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.where(valid, jnp.exp(logits - m_new), 0.0)  # [G, page]
    l_new = alpha * l_ref[...] + jnp.sum(probs, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        probs, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(p == pps - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "interpret"),
)
def paged_attention(
    q: jnp.ndarray,           # [B, H, D]
    k_pages: jnp.ndarray,     # [KVH, P, page, D]  (head-major page pool)
    v_pages: jnp.ndarray,     # [KVH, P, page, D]
    page_table: jnp.ndarray,  # [B, PPS] int32
    seq_lens: jnp.ndarray,    # [B] int32
    *,
    scale: float | None = None,
    softcap: float = 0.0,
    interpret: bool | None = None,   # None: repro.kernels.interpret()
) -> jnp.ndarray:
    """One decode step of paged GQA attention.  Returns [B, H, D]."""
    if interpret is None:
        interpret = kernels.interpret()
    b, h, d = q.shape
    kvh, _, page, _ = k_pages.shape
    pps = page_table.shape[1]
    g = h // kvh
    scale = float(scale if scale is not None else d ** -0.5)

    qg = q.reshape(b, kvh, g, d)

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=float(softcap), page=page, pps=pps
    )

    def kv_index(bi, khi, pi, table_ref, lens_ref):
        return (khi, table_ref[bi, pi], 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kvh, pps),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda bi, khi, pi, *_: (bi, khi, 0, 0)),
                pl.BlockSpec((1, 1, page, d), kv_index),
                pl.BlockSpec((1, 1, page, d), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, d), lambda bi, khi, pi, *_: (bi, khi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_table, seq_lens, qg, k_pages, v_pages)
    return out.reshape(b, h, d)
