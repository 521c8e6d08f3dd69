"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.rc_transient import rc_multistep_pallas
from repro.kernels.row_cycle import row_cycle_fused_pallas
from repro.kernels.strap_gather import strap_attend_pallas


def random_ladder(rng, b, n, dtype):
    c = rng.uniform(1, 5, (b, n)).astype(dtype)
    g = rng.uniform(0.05, 0.2, (b, n - 1)).astype(dtype)
    gc = np.zeros((b, n), dtype)
    gc[:, 0] = 0.2
    vc = np.full((b, n), 0.55, dtype)
    v0 = rng.uniform(0, 1.1, (b, n)).astype(dtype)
    return map(jnp.asarray, (c, g, gc, vc, v0))


class TestRCTransientKernel:
    @pytest.mark.parametrize(
        "b,n,t",
        [(1, 6, 16), (130, 4, 25),
         pytest.param(9, 6, 50, marks=pytest.mark.slow),
         pytest.param(64, 8, 33, marks=pytest.mark.slow),
         pytest.param(256, 6, 10, marks=pytest.mark.slow)])
    def test_shapes(self, rng, b, n, t):
        c, g, gc, vc, v0 = random_ladder(rng, b, n, np.float32)
        ramp = jnp.asarray(np.clip(np.arange(t) / 8, 0, 1), jnp.float32)
        out_ref = ref.rc_multistep_ref(c, g, gc, vc, v0, ramp, 0.02)
        out_pl = rc_multistep_pallas(c, g, gc, vc, v0, ramp, 0.02,
                                     interpret=True)
        assert out_pl.shape == (t, b, n)
        np.testing.assert_allclose(np.array(out_ref), np.array(out_pl),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64])
    def test_dtypes(self, rng, dtype):
        if dtype == np.float64:
            pytest.skip("x64 disabled in test session")
        c, g, gc, vc, v0 = random_ladder(rng, 7, 6, dtype)
        ramp = jnp.ones((20,), dtype)
        out_ref = ref.rc_multistep_ref(c, g, gc, vc, v0, ramp, 0.01)
        out_pl = rc_multistep_pallas(c, g, gc, vc, v0, ramp, 0.01,
                                     interpret=True)
        np.testing.assert_allclose(np.array(out_ref), np.array(out_pl),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.slow
    def test_block_partitioning(self, rng):
        """Batch larger than one block must tile correctly (the fused
        engine's padded-tail test covers block tiling in the fast tier)."""
        c, g, gc, vc, v0 = random_ladder(rng, 300, 6, np.float32)
        ramp = jnp.ones((12,), jnp.float32)
        out_ref = ref.rc_multistep_ref(c, g, gc, vc, v0, ramp, 0.02)
        out_pl = rc_multistep_pallas(c, g, gc, vc, v0, ramp, 0.02,
                                     b_blk=128, interpret=True)
        np.testing.assert_allclose(np.array(out_ref), np.array(out_pl),
                                   rtol=1e-5, atol=1e-6)


def random_row_cycle_inputs(rng, b, n, dtype=np.float32):
    """Random fused-engine operands with realistic clamp networks."""
    c = rng.uniform(1, 5, (b, n)).astype(dtype)
    g = rng.uniform(0.05, 0.2, (b, n - 1)).astype(dtype)
    gc_res = np.zeros((b, n), dtype)
    gc_res[:, 0] = 0.125
    gc_pre = np.zeros((b, n), dtype)
    gc_pre[:, :n - 1] = 0.125
    v0 = np.full((b, n), 0.55, dtype)
    v0[:, n - 1] = 1.0
    params = np.stack([
        rng.uniform(0.5, 4.0, b),       # tau_wl
        rng.uniform(0.01, 0.2, b),      # thr_rel
        np.full(b, 1.1),                # vdd
        np.full(b, 0.55),               # vpre
        np.ones(b),                     # active
    ], axis=1).astype(dtype)
    return tuple(map(jnp.asarray, (c, g, gc_res, gc_pre, v0, params)))


def live_steps(evt, params, caps, dt):
    """Steps each standalone row runs before DONE, from its event times
    (the whole phase window where an event is NaN); 0 for inactive rows."""
    t = np.asarray(evt)[:, [0, 2, 3]]
    steps = np.where(np.isnan(t), caps, np.rint(t / dt)).sum(axis=1)
    return np.where(np.asarray(params)[:, 4] > 0.5, steps, 0)


class TestRowCycleFusedKernel:
    """Pallas fused ACT/RESTORE/PRE engine vs the jnp oracle."""

    DT = 0.02

    def check(self, args, n_act, n_res, n_pre, **kw):
        evt_ref, vend_ref = ref.row_cycle_fused_ref(
            *args, self.DT, n_act, n_res, n_pre)
        evt_pl, vend_pl = row_cycle_fused_pallas(
            *args, self.DT, n_act, n_res, n_pre, interpret=True, **kw)
        # event times must agree to within one integration step (usually
        # exactly; float32 noise at a threshold can flip one step); rows
        # that never cross a phase report NaN in BOTH engines
        t_ref = np.asarray(evt_ref)[:, [0, 2, 3]]
        t_pl = np.asarray(evt_pl)[:, [0, 2, 3]]
        np.testing.assert_array_equal(np.isnan(t_ref), np.isnan(t_pl))
        diff = np.where(np.isnan(t_ref), 0.0, np.abs(t_ref - t_pl))
        assert diff.max() <= self.DT + 1e-9
        np.testing.assert_allclose(np.asarray(evt_ref)[:, 1],
                                   np.asarray(evt_pl)[:, 1],
                                   rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(np.asarray(vend_ref),
                                   np.asarray(vend_pl),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("b,n,n_act,n_res,n_pre",
                             [(9, 6, 30, 15, 10), (64, 8, 18, 12, 10),
                              pytest.param(1, 6, 20, 18, 12,
                                           marks=pytest.mark.slow),
                              pytest.param(130, 4, 20, 15, 10,
                                           marks=pytest.mark.slow),
                              pytest.param(256, 6, 16, 12, 10,
                                           marks=pytest.mark.slow)])
    def test_shapes_and_phase_durations(self, rng, b, n, n_act, n_res, n_pre):
        args = random_row_cycle_inputs(rng, b, n)
        self.check(args, n_act, n_res, n_pre)

    @pytest.mark.parametrize("b,b_blk", [(150, 128), (2048 + 150, 1024)])
    def test_padded_batch_tail(self, rng, b, b_blk):
        """A multi-block grid with a padded last block: 150 rows at 128-row
        blocks, and a 2,048-row chunk plus 150 at full (8, 128) blocks of
        1,024 rows; inactive padding rows must not perturb live points."""
        args = random_row_cycle_inputs(rng, b, 6)
        self.check(args, 12, 10, 8, b_blk=b_blk)

    def test_replica_pairs_at_lane_and_sublane_edges(self, rng):
        """[replica, main] pairs at lanes 126/127 and at lanes 0/1 of the
        next sublane: each main row takes its ACT crossing from the replica
        one lane before it, never from a neighbouring pair."""
        b = 384
        args = list(random_row_cycle_inputs(rng, b, 6))
        params = np.array(args[5])
        role = np.zeros(b)
        pairs = (126, 128, 254, 256)
        for r in pairs:
            role[r], role[r + 1] = 1.0, 2.0
        # one WL ramp, replica thresholds apart: a borrowed crossing shows
        for k, r in enumerate(pairs):
            params[r:r + 2, 0] = 1.0
            params[r, 1] = 0.004 + 0.009 * k
        args[5] = jnp.asarray(np.column_stack([params, role]).astype(np.float32))
        self.check(args, 60, 15, 10)
        evt, _ = row_cycle_fused_pallas(*args, self.DT, 60, 15, 10,
                                        interpret=True)
        t_dev = np.asarray(evt)[:, 0]
        fire = [t_dev[r] for r in pairs]
        assert np.isfinite(fire).all() and len(set(fire)) == len(pairs)
        np.testing.assert_array_equal(t_dev[[r + 1 for r in pairs]], fire)
        # a replica is ACT-only: no RESTORE or PRE event
        np.testing.assert_array_equal(np.asarray(evt)[list(pairs), 2:], 0.0)

    def test_block_rows_must_fill_whole_lanes(self, rng):
        args = random_row_cycle_inputs(rng, 8, 6)
        with pytest.raises(ValueError, match="b_blk=64"):
            row_cycle_fused_pallas(*args, self.DT, 4, 4, 4, b_blk=64)

    def test_inactive_points_never_step(self, rng):
        """active=0 rows start DONE: zero event times, untouched state."""
        args = list(random_row_cycle_inputs(rng, 8, 6))
        params = np.array(args[5])
        params[3:, 4] = 0.0
        args[5] = jnp.asarray(params)
        evt, v_end = row_cycle_fused_pallas(*args, self.DT, 10, 10, 10,
                                            interpret=True)
        np.testing.assert_array_equal(np.asarray(evt)[3:], 0.0)
        np.testing.assert_allclose(np.asarray(v_end)[3:],
                                   np.asarray(args[4])[3:])

    def test_block_steps_are_each_blocks_slowest_row(self, rng):
        """The kernel's own trip count of each block is the most live steps
        of any of its rows, counted from the events; inactive rows and the
        padding of the last block add nothing.  The oracle reports its own
        loop's count, with the whole batch as one block."""
        caps = (60, 60, 60)
        b, blk = 400, 128
        args = list(random_row_cycle_inputs(rng, b, 6))
        params = np.array(args[5])
        params[blk:2 * blk, 4] = 0.0             # block 1: no live row
        args[5] = jnp.asarray(params)
        out = row_cycle_fused_pallas(*args, self.DT, *caps, b_blk=blk,
                                     interpret=True)
        live = live_steps(out[0], params, caps, self.DT)
        want = [live[lo:lo + blk].max() for lo in range(0, b, blk)]
        assert out.block_steps.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out.block_steps), want)
        assert want[1] == 0 and 0 < min(want[::2]) < max(want) < sum(caps)
        out_ref = ref.row_cycle_fused_ref(*args, self.DT, *caps)
        np.testing.assert_array_equal(
            np.asarray(out_ref.block_steps),
            [live_steps(out_ref[0], params, caps, self.DT).max()])

    def test_timeout_is_nan_not_phase_window(self, rng):
        """An uncrossable ACT threshold must report NaN — an older revision
        clamped the event to the phase window, silently aliasing timeouts
        with legitimate last-step crossings."""
        args = list(random_row_cycle_inputs(rng, 4, 6))
        params = np.array(args[5])
        params[:, 1] = 1e9                    # thr_rel no signal can reach
        args[5] = jnp.asarray(params)
        n_act = 15
        for run in (row_cycle_fused_pallas, None):
            evt, _ = (
                ref.row_cycle_fused_ref(*args, self.DT, n_act, 10, 10)
                if run is None
                else run(*args, self.DT, n_act, 10, 10, interpret=True))
            assert np.isnan(np.asarray(evt)[:, 0]).all()

    def test_last_step_crossing_stays_finite(self, rng):
        """The flip side of NaN timeouts: a crossing that lands exactly on
        the final ACT step must report the finite n_act*dt, not NaN."""
        args = list(random_row_cycle_inputs(rng, 4, 6))
        params = np.array(args[5])
        params[:, 1] = 1e-6                   # crosses on the first step
        args[5] = jnp.asarray(params)
        # find each row's natural crossing step, then shrink the window to
        # end exactly there for row 0
        evt_pl, _ = row_cycle_fused_pallas(*args, self.DT, 30, 10, 10,
                                           interpret=True)
        n_cross = int(round(float(np.asarray(evt_pl)[0, 0]) / self.DT))
        evt, _ = row_cycle_fused_pallas(*args, self.DT, n_cross, 10, 10,
                                        interpret=True)
        t0 = float(np.asarray(evt)[0, 0])
        assert np.isfinite(t0)
        np.testing.assert_allclose(t0, n_cross * self.DT, rtol=1e-6)


def test_unknown_backend_raises(rng):
    """A misspelt backend is an error, never a silent fall to the oracle."""
    from repro.kernels import ops
    args = random_row_cycle_inputs(rng, 8, 6)
    with pytest.raises(ValueError, match="backend='pallsa'"):
        ops.row_cycle_fused(*args, 0.02, 4, 4, 4, backend="pallsa")


class TestTridiag:
    @pytest.mark.parametrize("b,n", [(1, 3), (5, 7), (16, 32)])
    def test_vs_dense_solve(self, rng, b, n):
        d = rng.uniform(2, 4, (b, n))
        dl = rng.uniform(-1, 0, (b, n)); dl[:, 0] = 0
        du = rng.uniform(-1, 0, (b, n)); du[:, -1] = 0
        rhs = rng.normal(size=(b, n))
        x = np.array(ref.tridiag_solve_ref(*map(jnp.asarray,
                                                (dl, d, du, rhs))))
        for i in range(b):
            a = np.diag(d[i]) + np.diag(dl[i, 1:], -1) + np.diag(du[i, :-1], 1)
            np.testing.assert_allclose(a @ x[i], rhs[i], rtol=1e-4,
                                       atol=1e-5)


class TestStrapAttendKernel:
    @pytest.mark.parametrize(
        "b,p,page,hkv,d,hq,g",
        [(2, 8, 16, 2, 64, 8, 2),
         pytest.param(1, 4, 8, 1, 128, 4, 4, marks=pytest.mark.slow),
         pytest.param(3, 6, 32, 3, 32, 6, 3, marks=pytest.mark.slow),
         pytest.param(2, 16, 8, 4, 64, 16, 4, marks=pytest.mark.slow),
         pytest.param(1, 8, 128, 2, 128, 2, 2, marks=pytest.mark.slow)])
    def test_shapes(self, rng, b, p, page, hkv, d, hq, g):
        s = p // g
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, p, page, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, p, page, hkv, d)), jnp.float32)
        ids = np.stack([rng.permutation(p // g)[:s] for _ in range(b)])
        if s > 1:
            ids[0, -1] = -1                       # masked strap
        ids = jnp.asarray(ids, jnp.int32)
        o_ref = ref.strap_attend_ref(q, k, v, ids, g)
        o_pl = strap_attend_pallas(q, k, v, ids, g, interpret=True)
        np.testing.assert_allclose(np.array(o_ref), np.array(o_pl),
                                   rtol=3e-5, atol=3e-5)

    @pytest.mark.slow
    def test_bf16(self, rng):
        b, p, page, hkv, d, hq, g = 2, 4, 16, 2, 64, 4, 2
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(b, p, page, hkv, d)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(b, p, page, hkv, d)), jnp.bfloat16)
        ids = jnp.asarray([[0, 1], [1, 0]], jnp.int32)
        o_ref = ref.strap_attend_ref(q, k, v, ids, g)
        o_pl = strap_attend_pallas(q, k, v, ids, g, interpret=True)
        np.testing.assert_allclose(np.array(o_ref, np.float32),
                                   np.array(o_pl, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_subset_equals_dense_subset(self, rng):
        """Gated attention over straps S == dense attention over exactly
        those tokens."""
        b, p, page, hkv, d, hq, g = 1, 8, 4, 1, 16, 2, 2
        q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, p, page, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, p, page, hkv, d)), jnp.float32)
        ids = jnp.asarray([[1, 3]], jnp.int32)
        o = np.array(ref.strap_attend_ref(q, k, v, ids, g))
        # dense oracle over tokens of straps 1,3 (pages 2,3,6,7)
        sel_pages = [2, 3, 6, 7]
        kk = np.array(k)[:, sel_pages].reshape(b, -1, hkv, d)
        vv = np.array(v)[:, sel_pages].reshape(b, -1, hkv, d)
        scale = d ** -0.5
        qq = np.array(q).reshape(b, hkv, hq // hkv, d)
        logits = np.einsum("bhgd,bshd->bhgs", qq, kk) * scale
        w = np.exp(logits - logits.max(-1, keepdims=True))
        w = w / w.sum(-1, keepdims=True)
        oo = np.einsum("bhgs,bshd->bhgd", w, vv).reshape(b, hq, d)
        np.testing.assert_allclose(o, oo, rtol=1e-5, atol=1e-5)
