"""Pallas TPU kernel: selector+strap gated KV gather + flash-decode attention.

TPU adaptation of the paper's "BL Selector + Strap" (Fig. 2d): the KV cache
is paged in HBM; pages are grouped into *straps* (G consecutive pages).  A
*selector* chooses which straps participate in a decode step; only selected
straps are streamed HBM -> VMEM, exactly like the IGO selector keeping
unselected local bitlines off the global line.  HBM bytes per decoded token
drop by the strap selectivity (the C_BL 20 fF -> 6.6 fF analogue).

Layout / schedule:
  K/V are relaid head-major, (B, Hkv, P*page, D), so a strap is one
  (G*page, D) block whose last two dims meet the TPU (8, 128) tiling.
  grid = (B, Hkv, S)          S = number of selected straps per sequence
  The strap axis is the innermost (sequential, "arbitrary") grid dim; the
  kernel keeps the online-softmax state (m, l, o-accumulator) for the
  (batch, kv-head) tile in VMEM scratch across strap steps and writes the
  normalized output on the last strap.
  Page indices arrive via scalar prefetch (PrefetchScalarGridSpec) so the
  index-mapped BlockSpec can fetch k/v blocks straight from HBM at block
  granularity — i.e. the gather *is* the block index map; no materialized
  gathered copy ever exists in HBM.

q heads are grouped GQA-style: the Hq/Hkv query heads of a kv head form
one (grp, D) query tile against the strap's (G*page, D) K/V tile.
Masked straps (id < 0) contribute nothing (handled by -inf masking).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _strap_kernel(strap_ids_ref,          # scalar prefetch: (B, S)
                  lengths_ref,            # scalar prefetch: (B,)
                  q_ref,                  # (1, grp, D)
                  k_ref,                  # (1, 1, G*page, D)
                  v_ref,                  # (1, 1, G*page, D)
                  o_ref,                  # (1, grp, D)
                  m_ref, l_ref, acc_ref,  # VMEM scratch
                  *, scale: float, num_straps: int, blk: int):
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    strap_id = strap_ids_ref[b, s]
    valid = strap_id >= 0

    q = q_ref[0, 0].astype(jnp.float32)                 # (grp, D)
    k = k_ref[0, 0].astype(jnp.float32)                 # (T_blk, D)
    v = v_ref[0, 0].astype(jnp.float32)                 # (T_blk, D)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # (grp, T_blk)
    # token-level mask: a partially filled strap has zero-padding tokens at
    # flat positions >= lengths[b]; their logit would be a perfectly valid
    # q.0 = 0 and they'd steal softmax mass, so mask them like the dense path
    tok_pos = strap_id * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    tok_ok = tok_pos < lengths_ref[b]                   # (1, blk)
    logits = jnp.where(tok_ok, logits, NEG_INF)

    m_prev = m_ref[...]                                 # (grp, 1)
    m_cur = jnp.max(logits, axis=-1, keepdims=True)
    # an invalid (masked) strap must not move the running max
    m_cur = jnp.where(valid, m_cur, jnp.full_like(m_cur, NEG_INF))
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new)
    # zero p both for masked straps and masked tokens (the latter guards the
    # degenerate exp(NEG_INF - NEG_INF) = 1 case when nothing valid yet)
    p = jnp.where(valid & tok_ok, p, jnp.zeros_like(p))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(s == num_straps - 1)
    def _finalize():
        # guard against fully-masked selection (all straps -1): emit zeros
        l = l_ref[...]
        safe_l = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def strap_attend_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, strap_ids: jnp.ndarray,
                        pages_per_strap: int, scale: float | None = None,
                        lengths: jnp.ndarray | None = None,
                        *, interpret: bool = True) -> jnp.ndarray:
    """Pallas-backed equivalent of `ref.strap_attend_ref` -> (B, Hq, D).

    q         : (B, Hq, D)
    k_pages   : (B, P, page, Hkv, D)
    v_pages   : (B, P, page, Hkv, D)
    strap_ids : (B, S) int32, -1 = masked
    lengths   : (B,) int32 valid-token counts (None = every token valid)
    """
    b, p, page, hkv, d = k_pages.shape
    _, hq, _ = q.shape
    grp = hq // hkv
    s = strap_ids.shape[1]
    g = pages_per_strap
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # flatten pages to a head-major token axis (B, Hkv, P*page, D): a strap
    # is a contiguous block of G*page tokens, so the index map can address
    # it directly, and the block's last two dims (G*page, D) meet the TPU
    # tiling rule.  The relayout is one pass over the cache per call.
    k_flat = k_pages.reshape(b, p * page, hkv, d).transpose(0, 2, 1, 3)
    v_flat = v_pages.reshape(b, p * page, hkv, d).transpose(0, 2, 1, 3)
    q_g = q.reshape(b, hkv, grp, d)
    blk = g * page

    raw_ids = strap_ids.astype(jnp.int32)
    if lengths is None:
        lengths = jnp.full((b,), p * page, jnp.int32)   # all tokens valid
    lengths = lengths.astype(jnp.int32)

    # NOTE: with PrefetchScalarGridSpec the index maps receive
    # (*grid_indices, *scalar_prefetch_refs).  Masked ids (-1) are clamped
    # to 0 *only for addressing*; the kernel sees the raw id for validity.
    def q_map(bi, hi, si, ids, lens):
        del ids, lens, si
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, si, ids, lens):
        del lens
        return (bi, hi, jnp.maximum(ids[bi, si], 0), 0)

    def o_map(bi, hi, si, ids, lens):
        del ids, lens, si
        return (bi, hi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, s),
        in_specs=[
            pl.BlockSpec((1, 1, grp, d), q_map),
            pl.BlockSpec((1, 1, blk, d), kv_map),
            pl.BlockSpec((1, 1, blk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, grp, d), o_map),
        scratch_shapes=[
            pltpu.VMEM((grp, 1), jnp.float32),
            pltpu.VMEM((grp, 1), jnp.float32),
            pltpu.VMEM((grp, d), jnp.float32),
        ],
    )

    kernel = functools.partial(_strap_kernel, scale=scale, num_straps=s,
                               blk=blk)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, grp, d), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(raw_ids, lengths, q_g, k_flat, v_flat)
    return out.reshape(b, hq, d)
