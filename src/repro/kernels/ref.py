"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the Pallas implementations are validated against
(tests sweep shapes/dtypes and assert allclose).  They are also the default
execution path on CPU, where `interpret=True` Pallas is slower.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Batched tridiagonal solve (Thomas algorithm)
# --------------------------------------------------------------------------

def tridiag_solve_ref(dl: jnp.ndarray, d: jnp.ndarray, du: jnp.ndarray,
                      b: jnp.ndarray) -> jnp.ndarray:
    """Solve A x = b for tridiagonal A, batched over leading dims.

    dl: (..., N) sub-diagonal, dl[..., 0] ignored
    d : (..., N) main diagonal
    du: (..., N) super-diagonal, du[..., N-1] ignored
    b : (..., N) right-hand side
    """
    n = d.shape[-1]

    def fwd(carry, idx):
        cp_prev, dp_prev = carry
        denom = d[..., idx] - dl[..., idx] * cp_prev
        cp = du[..., idx] / denom
        dp = (b[..., idx] - dl[..., idx] * dp_prev) / denom
        return (cp, dp), (cp, dp)

    denom0 = d[..., 0]
    cp0 = du[..., 0] / denom0
    dp0 = b[..., 0] / denom0
    (_, _), (cps, dps) = jax.lax.scan(fwd, (cp0, dp0), jnp.arange(1, n))
    # stack cp/dp including index 0; cps has shape (n-1, ...)
    cps = jnp.concatenate([cp0[None], cps], axis=0)
    dps = jnp.concatenate([dp0[None], dps], axis=0)

    def bwd(x_next, idx):
        x = dps[idx] - cps[idx] * x_next
        return x, x

    xn = dps[n - 1]
    _, xs = jax.lax.scan(bwd, xn, jnp.arange(n - 2, -1, -1))
    xs = jnp.concatenate([xn[None], xs], axis=0)[::-1]
    # move node axis back to the end
    return jnp.moveaxis(xs, 0, -1)


# --------------------------------------------------------------------------
# RC-ladder multistep implicit-Euler transient (the SPICE inner loop)
# --------------------------------------------------------------------------

def rc_multistep_ref(c: jnp.ndarray, g_branch: jnp.ndarray,
                     g_clamp: jnp.ndarray, v_clamp: jnp.ndarray,
                     v0: jnp.ndarray, ramp: jnp.ndarray,
                     dt: float) -> jnp.ndarray:
    """Simulate T implicit-Euler steps of a batched RC ladder.

    The ladder has N nodes; branch i connects node i and i+1 with
    conductance g_branch[..., i].  The LAST branch (index N-2, the cell
    access transistor) is scaled by `ramp[t]` at step t (WL ramp).  Each
    node may additionally be clamped toward v_clamp through g_clamp.

    c        : (B, N)   node capacitances            [fF]
    g_branch : (B, N-1) branch conductances          [1/kOhm]
    g_clamp  : (B, N)   clamp conductances           [1/kOhm]
    v_clamp  : (B, N)   clamp target voltages        [V]
    v0       : (B, N)   initial node voltages        [V]
    ramp     : (T,)     access-branch scale per step (0..1)
    dt       : step     [ns]    (fF/kOhm -> ps, so G uses 1e-3 factor)

    Returns trace: (T, B, N) node voltages after each step.
    """
    cdt = c / dt * 1e-3  # fF/ns = uS; G is in 1/kOhm = mS -> scale by 1e-3

    def step(v, s):
        # scale the access (last) branch by the WL ramp value for this step
        g = jnp.concatenate([g_branch[..., :-1], g_branch[..., -1:] * s], axis=-1)
        # assemble tridiagonal A = C/dt + G
        zeros = jnp.zeros_like(c[..., :1])
        g_lo = jnp.concatenate([zeros, g], axis=-1)        # g[i-1] at row i
        g_hi = jnp.concatenate([g, zeros], axis=-1)        # g[i]   at row i
        d = cdt + g_lo + g_hi + g_clamp
        dl = jnp.concatenate([zeros, -g], axis=-1)
        du = jnp.concatenate([-g, zeros], axis=-1)
        rhs = cdt * v + g_clamp * v_clamp
        v_next = tridiag_solve_ref(dl, d, du, rhs)
        return v_next, v_next

    _, trace = jax.lax.scan(step, v0, ramp)
    return trace


# --------------------------------------------------------------------------
# Fused ACT/RESTORE/PRE row-cycle engine (event-driven, trace-free)
# --------------------------------------------------------------------------

# params / events column layouts (shared with kernels.row_cycle)
(_PAR_TAU_WL, _PAR_THR_REL, _PAR_VDD, _PAR_VPRE, _PAR_ACTIVE,
 _PAR_ROLE) = range(6)
ROW_CYCLE_N_PARAMS = 6
ROW_CYCLE_N_EVENTS = 4
_RESTORE_FRAC = 0.95
_EQUALIZE_TOL_V = 5e-3

# _PAR_ROLE values: how a row's SA enable is timed during ACT.
ROLE_STANDALONE = 0.0   # fixed timing: fires on the row's own 0.9 crossing
ROLE_REPLICA = 1.0      # replica bitline: fires the SA enable of row+1,
                        # then jumps straight to DONE (no RESTORE/PRE)
ROLE_MAIN = 2.0         # main array row: SA enable fired by the replica
                        # at row-1 (rows are interleaved [replica, main])


class RowCycleOut(tuple):
    """What one fused row-cycle call returns: unpacks as `(events, v_end)`,
    and carries `block_steps`, the engine's own step count of each batch
    block, (n_blocks,) int32.  A pytree of those three arrays, so it passes
    through `jax.jit`."""

    def __new__(cls, events, v_end, block_steps):
        out = super().__new__(cls, (events, v_end))
        out.block_steps = block_steps
        return out


jax.tree_util.register_pytree_node(
    RowCycleOut, lambda o: ((o[0], o[1], o.block_steps), None),
    lambda _, leaves: RowCycleOut(*leaves))


def _thomas_small(dl, d, du, rhs):
    """Thomas solve unrolled over the last (static, small) axis."""
    n = d.shape[-1]
    cp = [None] * n
    dp = [None] * n
    cp[0] = du[..., 0] / d[..., 0]
    dp[0] = rhs[..., 0] / d[..., 0]
    for i in range(1, n):
        denom = d[..., i] - dl[..., i] * cp[i - 1]
        cp[i] = du[..., i] / denom
        dp[i] = (rhs[..., i] - dl[..., i] * dp[i - 1]) / denom
    x = [None] * n
    x[n - 1] = dp[n - 1]
    for i in range(n - 2, -1, -1):
        x[i] = dp[i] - cp[i] * x[i + 1]
    return jnp.stack(x, axis=-1)


def row_cycle_fused_ref(c: jnp.ndarray, g_branch: jnp.ndarray,
                        gc_res: jnp.ndarray, gc_pre: jnp.ndarray,
                        v0: jnp.ndarray, params: jnp.ndarray,
                        dt: float, n_act: int, n_res: int, n_pre: int):
    """Oracle for the fused row-cycle engine: one pass over ACT/RESTORE/PRE.

    Each design point runs its own phase state machine
    (0=ACT, 1=RESTORE, 2=PRE, 3=DONE):

      ACT    : access branch scaled by the rising WL ramp 1 - e^{-t/tau};
               advances when v[0] - vpre >= thr_rel or after n_act steps.
      RESTORE: access branch fully on, clamp (gc_res -> vdd);
               advances when v[N-1] >= 0.95 * vdd or after n_res steps.
      PRE    : falling WL ramp e^{-t/tau}, clamp (gc_pre -> vpre);
               done when max |v[:N-1] - vpre| <= 5 mV or after n_pre steps.

    Event times are first-crossing times (idx+1)*dt measured from the phase
    start, or NaN on timeout (never crossed inside the phase window) —
    identical semantics to the phased `core.transient` reference, which
    this oracle (and the Pallas kernel validated against it) reproduces to
    within one dt.

    c, gc_res, gc_pre, v0 : (B, N);  g_branch : (B, N-1);  params : (B, 5)
    with columns [tau_wl_ns, thr_rel_v, vdd, vpre, active], or (B, 6) with
    a trailing role column (see ROLE_*).  Replica-closed timing interleaves
    rows as [replica, main] pairs: the replica's own ACT crossing fires the
    SA enable of the main row directly after it, and the replica then skips
    RESTORE/PRE (phase 0 -> 3).  A main row's recorded dv_sense is its own
    developed signal at the moment the replica fires.

    Returns a `RowCycleOut`: events (B, 4) [t_dev, dv_sense, t_res_dur,
    t_pre], v_end (B, N) final node voltages, and `block_steps` (1,), the
    loop's trip count with the whole batch as one block.
    """
    b, n = c.shape
    cdt = c / dt * 1e-3  # fF/ns = uS; G in 1/kOhm = mS -> 1e-3 factor
    tau = jnp.maximum(params[:, _PAR_TAU_WL], 1e-3)
    thr_rel = params[:, _PAR_THR_REL]
    vdd = params[:, _PAR_VDD]
    vpre = params[:, _PAR_VPRE]
    active = params[:, _PAR_ACTIVE] > 0.5
    role = (params[:, _PAR_ROLE] if params.shape[1] > _PAR_ROLE
            else jnp.zeros_like(tau))      # static: role column presence
    is_rep = jnp.abs(role - ROLE_REPLICA) < 0.5
    is_main = role > ROLE_MAIN - 0.5
    t_total = n_act + n_res + n_pre
    caps = jnp.asarray([n_act, n_res, n_pre], jnp.int32)

    def cond(state):
        t, phase, _, _, _ = state
        return jnp.logical_and(t < t_total, jnp.any(phase < 3))

    def body(state):
        t, phase, tin, v, evt = state
        in_act = phase == 0
        in_res = phase == 1
        in_pre = phase == 2
        done = phase >= 3

        t_ns = (tin.astype(jnp.float32) + 1.0) * dt
        e = jnp.exp(-t_ns / tau)
        s = jnp.where(in_act, 1.0 - e,
                      jnp.where(in_res, 1.0, jnp.where(in_pre, e, 0.0)))
        gc = jnp.where(in_res[:, None], gc_res,
                       jnp.where(in_pre[:, None], gc_pre, 0.0))
        gcv = jnp.where(in_res[:, None], gc_res * vdd[:, None],
                        jnp.where(in_pre[:, None],
                                  gc_pre * vpre[:, None], 0.0))

        g = jnp.concatenate(
            [g_branch[:, : n - 2], g_branch[:, n - 2:] * s[:, None]], axis=1)
        zeros = jnp.zeros_like(c[:, :1])
        g_lo = jnp.concatenate([zeros, g], axis=1)
        g_hi = jnp.concatenate([g, zeros], axis=1)
        d = cdt + g_lo + g_hi + gc
        dl = jnp.concatenate([zeros, -g], axis=1)
        du = jnp.concatenate([-g, zeros], axis=1)
        v_sol = _thomas_small(dl, d, du, cdt * v + gcv)
        v_next = jnp.where(done[:, None], v, v_sol)

        # SA-enable coupling: a main row's ACT crossing is the crossing of
        # the replica at row-1 (replica/main pairs run ACT in lockstep, so
        # the stateless shift is exact — the main never advances first).
        cross_own = v_next[:, 0] - vpre >= thr_rel
        cross_prev = jnp.concatenate([cross_own[-1:], cross_own[:-1]])
        cross = jnp.stack([
            jnp.where(is_main, cross_prev, cross_own),
            v_next[:, n - 1] >= _RESTORE_FRAC * vdd,
            jnp.max(jnp.abs(v_next[:, : n - 1] - vpre[:, None]),
                    axis=-1) <= _EQUALIZE_TOL_V,
        ])
        tin1 = tin + 1
        phase_c = jnp.clip(phase, 0, 2)
        crossed = jnp.take_along_axis(cross, phase_c[None, :], axis=0)[0]
        cap = caps[phase_c]
        advance = jnp.logical_and(~done,
                                  jnp.logical_or(crossed, tin1 >= cap))
        t_evt = jnp.where(crossed, tin1.astype(jnp.float32) * dt,
                          jnp.float32(jnp.nan))

        rec = lambda ph: jnp.logical_and(advance, phase == ph)
        evt = evt.at[:, 0].set(jnp.where(rec(0), t_evt, evt[:, 0]))
        evt = evt.at[:, 1].set(
            jnp.where(rec(0), v_next[:, 0] - vpre, evt[:, 1]))
        evt = evt.at[:, 2].set(jnp.where(rec(1), t_evt, evt[:, 2]))
        evt = evt.at[:, 3].set(jnp.where(rec(2), t_evt, evt[:, 3]))

        # replica rows are ACT-only: they jump straight to DONE
        phase_inc = jnp.where(is_rep, 3, 1)
        phase = jnp.where(advance, phase + phase_inc, phase)
        tin = jnp.where(advance, 0, jnp.where(done, tin, tin1))
        return t + 1, phase, tin, v_next, evt

    state = (jnp.int32(0), jnp.where(active, 0, 3).astype(jnp.int32),
             jnp.zeros((b,), jnp.int32), v0.astype(jnp.float32),
             jnp.zeros((b, ROW_CYCLE_N_EVENTS), jnp.float32))
    t_fin, _, _, v_fin, evt_fin = jax.lax.while_loop(cond, body, state)
    return RowCycleOut(evt_fin, v_fin, t_fin.reshape(1))


# --------------------------------------------------------------------------
# Selector+strap gated KV gather + flash-decode attention
# --------------------------------------------------------------------------

def strap_attend_ref(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                     strap_ids: jnp.ndarray, pages_per_strap: int,
                     scale: float | None = None,
                     lengths: jnp.ndarray | None = None) -> jnp.ndarray:
    """Oracle for the StrapCache gated decode attention.

    q         : (B, Hq, D)                 one query token per sequence
    k_pages   : (B, P, page, Hkv, D)       paged keys   (P = pages per seq)
    v_pages   : (B, P, page, Hkv, D)       paged values
    strap_ids : (B, S)                     selected strap indices (int32);
                strap s covers pages [s*G, (s+1)*G).  Entries may be -1
                (= strap masked out).
    lengths   : (B,) int32, optional       tokens actually written per
                sequence; positions >= lengths[b] are padding and masked
                out even when their strap is selected (a partially-filled
                strap holds zero-initialised pages whose logit would
                otherwise be 0, not -inf).
    Returns   : (B, Hq, D) attention output over exactly the selected straps.
    """
    b, p, page, hkv, dh = k_pages.shape
    bq, hq, _ = q.shape
    assert bq == b
    grp = hq // hkv
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    g = pages_per_strap

    # Build a per-page mask from the selected straps.
    page_strap = jnp.arange(p) // g                      # (P,)
    sel = strap_ids[..., None] == page_strap[None, None, :]   # (B, S, P)
    valid = (strap_ids >= 0)[..., None]
    page_mask = jnp.any(sel & valid, axis=1)             # (B, P)
    token_mask = jnp.repeat(page_mask, page, axis=1)     # (B, P*page)
    if lengths is not None:
        pos = jnp.arange(p * page)[None, :]              # (1, P*page)
        token_mask = token_mask & (pos < lengths[:, None])

    k = k_pages.reshape(b, p * page, hkv, dh)
    v = v_pages.reshape(b, p * page, hkv, dh)
    qg = q.reshape(b, hkv, grp, dh)
    logits = jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(token_mask[:, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", w, v.astype(jnp.float32))
    return o.reshape(b, hq, dh)
