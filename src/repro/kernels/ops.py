"""Jit'd public wrappers for the Pallas kernels, with backend dispatch.

`backend="auto"` picks the compiled Pallas kernel on TPU and the pure-jnp
oracle elsewhere (where `interpret=True` Pallas is a Python-level
interpreter and much slower than XLA:CPU).  On a TPU a kernel either
compiles or raises: nothing falls back to the interpreter or the oracle.
An explicit `backend="pallas"` off the TPU runs the interpreter — the tests
use it to validate the kernels against the oracles.
"""

from __future__ import annotations

import functools

import jax

from . import ref
from .rc_transient import rc_multistep_pallas
from .row_cycle import block_rows, row_cycle_fused_pallas
from .strap_gather import strap_attend_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(backend: str) -> bool:
    """Resolve `backend` ("auto" | "pallas" | "ref") to a kernel choice."""
    if backend == "auto":
        return _on_tpu()
    if backend not in ("pallas", "ref"):
        raise ValueError(f"backend={backend!r}: expected 'auto', 'pallas' "
                         "or 'ref'")
    return backend == "pallas"


@functools.partial(jax.jit, static_argnames=("dt", "backend"))
def rc_multistep(c, g_branch, g_clamp, v_clamp, v0, ramp, dt,
                 backend: str = "auto"):
    """Batched RC-ladder implicit-Euler transient -> (T, B, N) trace."""
    if _use_pallas(backend):
        return rc_multistep_pallas(c, g_branch, g_clamp, v_clamp, v0, ramp,
                                   dt, interpret=not _on_tpu())
    return ref.rc_multistep_ref(c, g_branch, g_clamp, v_clamp, v0, ramp, dt)


@functools.partial(jax.jit, static_argnames=("dt", "n_act", "n_res",
                                             "n_pre", "backend"))
def row_cycle_fused(c, g_branch, gc_res, gc_pre, v0, params, dt,
                    n_act, n_res, n_pre, backend: str = "auto"):
    """Fused ACT/RESTORE/PRE row-cycle engine -> `ref.RowCycleOut`:
    (events (B,4), v_end (B,N)), with the step count of each batch block
    (`row_cycle_block_rows` rows) as `.block_steps`.

    Trace-free: O(B) outputs regardless of the number of time steps.  See
    `ref.row_cycle_fused_ref` for the params layout and event semantics.
    The Pallas kernel runs on (8, 128) tiles with the batch on the lanes;
    the (B, w) <-> (w, B/128, 128) transposes live inside this jit.
    """
    if _use_pallas(backend):
        return row_cycle_fused_pallas(c, g_branch, gc_res, gc_pre, v0,
                                      params, dt, n_act, n_res, n_pre,
                                      interpret=not _on_tpu())
    return ref.row_cycle_fused_ref(c, g_branch, gc_res, gc_pre, v0, params,
                                   dt, n_act, n_res, n_pre)


def row_cycle_block_rows(n: int, backend: str = "auto") -> int:
    """Rows per batch block of a `row_cycle_fused` call over n rows: on the
    Pallas kernel n rounded up to whole 128-lane sublanes, at most 1,024
    (one (8, 128) tile per quantity); on the oracle the whole batch."""
    return block_rows(n) if _use_pallas(backend) else n


@functools.partial(jax.jit, static_argnames=("pages_per_strap", "scale", "backend"))
def strap_attend(q, k_pages, v_pages, strap_ids, pages_per_strap,
                 scale=None, backend: str = "auto", lengths=None):
    """Selector+strap gated decode attention -> (B, Hq, D).

    `lengths` ((B,) int32, optional) is the valid token count per sequence;
    tokens at flat positions >= lengths[b] are padding inside a partially
    filled strap and are masked out of the softmax.  `None` attends every
    token of every selected strap (all-valid).
    """
    if _use_pallas(backend):
        return strap_attend_pallas(q, k_pages, v_pages, strap_ids,
                                   pages_per_strap, scale,
                                   lengths=lengths,
                                   interpret=not _on_tpu())
    return ref.strap_attend_ref(q, k_pages, v_pages, strap_ids,
                                pages_per_strap, scale, lengths=lengths)


def tridiag_solve(dl, d, du, b):
    """Batched Thomas solve (used standalone by the transient engine)."""
    return ref.tridiag_solve_ref(dl, d, du, b)
