"""Pallas TPU kernel: fused multi-step RC-ladder transient (SPICE inner loop).

This is the compute hot-spot of the paper's methodology: implicit-Euler
time-stepping of batched tridiagonal RC networks (bitline ladders), swept
over thousands of design points by the DSE.

TPU adaptation (vs. a CUDA SPICE engine): instead of one-thread-per-netlist
with shared-memory staging, we tile the *design batch* across the grid and
keep the (B_blk, N) ladder state resident in VMEM across all T time steps —
the HBM traffic is one read of the netlist plus the trace write.  The
Thomas recurrences are sequential in N (N is small: 6-8 nodes) but
vectorized across the batch, which is the sublane axis of each column.

Grid:      (ceil(B / B_BLK), ceil(T / T_BLK)) — time is the inner,
           sequential axis; the ladder state is carried across time blocks
           in a (B_BLK, N) VMEM scratch.
BlockSpec: netlist operands blocked along batch only; `ramp` (T,) in SMEM;
           the trace is written one (T_BLK, B_BLK, N) block at a time.
Layout:    Mosaic lowers no rank-1 vectors, so every per-node quantity is a
           (B_BLK, 1) column and the state is a tuple of N columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_B_BLK = 128
T_BLK = 32      # time steps per grid step: (T_BLK, B_BLK, N) trace block


def _rc_kernel(c_ref, g_ref, gc_ref, vc_ref, v0_ref, ramp_ref, trace_ref,
               v_scr, *, t_blk: int, dt: float):
    """One (batch-block, time-block): T_BLK implicit-Euler steps."""
    col = lambda ref, j: ref[:, j:j + 1]                   # (B_blk, 1)
    n = c_ref.shape[-1]
    cdt = [col(c_ref, i) / dt * 1e-3 for i in range(n)]   # fF/ns -> mS
    g_br = [col(g_ref, i) for i in range(n - 1)]
    gc = [col(gc_ref, i) for i in range(n)]
    gcv = [gc[i] * col(vc_ref, i) for i in range(n)]
    t0 = pl.program_id(1) * t_blk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        v_scr[...] = v0_ref[...]

    def body(k, v):
        s = ramp_ref[t0 + k]
        # tridiagonal A = C/dt + G(s), access (last) branch scaled by s;
        # Thomas forward sweep (static N, unrolled: N is 6-8)
        g = g_br[:n - 2] + [g_br[n - 2] * s]
        cp, dp = [], []
        for i in range(n):
            lo = g[i - 1] if i > 0 else 0.0
            hi = g[i] if i < n - 1 else 0.0
            diag = cdt[i] + lo + hi + gc[i]
            rhs = cdt[i] * v[i] + gcv[i]
            if i == 0:
                cp.append(-hi / diag)
                dp.append(rhs / diag)
            else:
                denom = diag + lo * cp[i - 1]
                cp.append(-hi / denom)
                dp.append((rhs + lo * dp[i - 1]) / denom)
        # back substitution
        x = [None] * n
        x[n - 1] = dp[n - 1]
        for i in range(n - 2, -1, -1):
            x[i] = dp[i] - cp[i] * x[i + 1]
        for i in range(n):
            trace_ref[k, :, i:i + 1] = x[i]
        return tuple(x)

    v = jax.lax.fori_loop(0, t_blk, body,
                          tuple(col(v_scr, i) for i in range(n)))
    for i in range(n):
        v_scr[:, i:i + 1] = v[i]


def rc_multistep_pallas(c: jnp.ndarray, g_branch: jnp.ndarray,
                        g_clamp: jnp.ndarray, v_clamp: jnp.ndarray,
                        v0: jnp.ndarray, ramp: jnp.ndarray, dt: float,
                        *, b_blk: int = DEFAULT_B_BLK,
                        interpret: bool = True) -> jnp.ndarray:
    """Pallas-backed equivalent of `ref.rc_multistep_ref` -> (T, B, N)."""
    b, n = c.shape
    t = ramp.shape[0]
    b_blk = min(b_blk, b)
    t_blk = min(T_BLK, t)
    n_blocks = pl.cdiv(b, b_blk)
    n_tblocks = pl.cdiv(t, t_blk)

    # pad batch to a block multiple
    pad = n_blocks * b_blk - b
    if pad:
        padf = lambda x: jnp.pad(x, ((0, pad), (0, 0)), constant_values=1.0)
        c, g_branch, g_clamp, v_clamp, v0 = map(
            padf, (c, g_branch, g_clamp, v_clamp, v0))
    # pad time to a block multiple: the extra steps run after step T-1 and
    # are sliced off, so they cannot perturb the returned trace
    ramp = jnp.pad(ramp.astype(jnp.float32), (0, n_tblocks * t_blk - t),
                   mode="edge")

    kernel = functools.partial(_rc_kernel, t_blk=t_blk, dt=dt)
    bspec = lambda w: pl.BlockSpec((b_blk, w), lambda i, j: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks, n_tblocks),
        in_specs=[bspec(n), bspec(n - 1), bspec(n), bspec(n), bspec(n),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((t_blk, b_blk, n), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (n_tblocks * t_blk, n_blocks * b_blk, n), c.dtype),
        scratch_shapes=[pltpu.VMEM((b_blk, n), c.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(c, g_branch, g_clamp, v_clamp, v0, ramp)
    return out[:t, :b, :]
