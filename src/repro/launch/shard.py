"""Sharded multi-device driver for the fused row-cycle DSE sweep.

The array-native DSE layer already lowers a whole `DesignSpace` to ONE
flat operand batch (`transient.FusedOperands`, batch axis only).  The
single-host path then feeds that batch through the fused engine in a
*sequential* Python loop of `b_chunk`-sized dispatches.  This module
replaces that loop with a sharded dispatch:

    mesh    = make_sweep_mesh()                  # or any jax Mesh
    batch   = dse.sweep(space, sharding=mesh)    # each device: own slab

    # equivalently, via this module's convenience wrapper:
    batch   = shard.sharded_sweep(space, mesh=mesh)

Mechanics (the `pad_to` + `device_put` contract of `core.batch`):

1. the operand batch is padded with inactive design points so every
   device receives an identical, B_ALIGN-aligned slab (for grids larger
   than `n_devices * b_chunk`, a whole number of `b_chunk` chunks);
2. every operand is placed with a `NamedSharding` over the batch axis
   (`P(mesh.axis_names)` — a multi-axis mesh shards over the full device
   product, so `launch.mesh.make_test_mesh` works as-is);
3. a `shard_map`-wrapped engine call runs per device, chunking its local
   slab by `b_chunk` exactly like the sequential path — same compiled
   kernel shapes, same per-row arithmetic, hence bit-identical event
   times (the single-host sweep remains the equivalence oracle).

Under multi-process JAX (`jax.distributed.initialize` before any jax
import, then the same `dse.sweep(space, sharding=mesh)` call on every
host), the mesh spans all hosts and each process computes only its
addressable shards; operands are assembled per-shard from the
(host-replicated) lowered space via `jax.make_array_from_callback`.

Run `python -m repro.launch.shard --smoke` (with
`XLA_FLAGS=--xla_force_host_platform_device_count=N`) for the
sharded-vs-single-host bit-equivalence smoke `tools/ci_check.sh` uses.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import contracts, transient
from ..core.transient import (B_ALIGN, DT_NS, FusedOperands, N_ACT_STEPS,
                              N_PRE_STEPS, N_RESTORE_STEPS, RowCycleResult)
from ..kernels import ops
from .mesh import make_sweep_mesh

__all__ = [
    "sweep_sharding", "batch_sharding", "put_global",
    "row_cycle_fused_sharded", "simulate_row_cycle_sharded",
    "sharded_sweep_columns", "sharded_pareto_dominated",
    "sharded_sweep",
]


def _as_mesh(sharding) -> Mesh:
    """Normalize a `sharding=` argument (Mesh | NamedSharding | None).

    A `NamedSharding` must be equivalent to the canonical batch-axis
    sharding of its mesh — the driver always distributes the flat batch
    over the FULL device product, so a partial-axis spec would silently
    place operands differently than the caller asked; reject it instead.
    """
    if sharding is None:
        return make_sweep_mesh()
    if isinstance(sharding, NamedSharding):
        mesh = sharding.mesh
        canonical = NamedSharding(mesh, P(mesh.axis_names))
        if not sharding.is_equivalent_to(canonical, 2):
            raise ValueError(
                f"sharding spec {sharding.spec} does not shard the batch "
                f"axis over the mesh's full device product; pass the mesh "
                f"itself (or sweep_sharding(mesh) == {canonical.spec}) — "
                "partial-axis placement is not supported by the sweep "
                "driver")
        return mesh
    if isinstance(sharding, Mesh):
        return sharding
    raise TypeError(
        f"sharding must be a jax Mesh or NamedSharding, got {sharding!r}")


def sweep_sharding(sharding=None) -> NamedSharding:
    """The canonical sweep sharding: batch axis over ALL mesh axes.

    Accepts a Mesh (or None for a fresh all-device `make_sweep_mesh()`)
    and returns the `NamedSharding` that splits axis 0 over the mesh's
    full device product — regardless of how many named axes the mesh has.
    """
    mesh = _as_mesh(sharding)
    return NamedSharding(mesh, P(mesh.axis_names))


# `DesignBatch.device_put` alias for readers coming from core.batch docs
batch_sharding = sweep_sharding


def put_global(x, sharding: NamedSharding):
    """Place one (B, ...) array with the sweep sharding.

    Single-process: a plain `jax.device_put`.  Multi-process: every host
    holds the full lowered operand batch (the DesignSpace lowering is
    deterministic and host-replicated), so the global array is assembled
    from the local copy one addressable shard at a time.
    """
    x = jnp.asarray(x)
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_callback(
        x.shape, sharding, lambda idx: np.asarray(x[idx]))


def _dispatch_target(b: int, n_dev: int, b_chunk: int) -> int:
    """Padded batch size: identical per-device slabs, each a B_ALIGN
    multiple; slabs larger than `b_chunk` hold a whole number of chunks
    so in-device chunking never exceeds the memory bound."""
    slab = -(-b // n_dev)
    quantum = b_chunk if slab > b_chunk else B_ALIGN
    slab = -(-slab // quantum) * quantum
    return max(slab, B_ALIGN) * n_dev


@functools.lru_cache(maxsize=None)
def _sharded_engine(mesh: Mesh, backend: str, b_chunk: int):
    """jit(shard_map(...)) of the fused engine, cached per (mesh, backend,
    chunk).  Each device chunks its local slab by `b_chunk` — the same
    fixed compiled shapes as the sequential `_row_cycle_fused_chunked`
    loop, so per-row results are identical.  Multi-chunk slabs run the
    chunks through `lax.map` (one traced body, sequential execution per
    device), so trace/compile cost stays O(one chunk) however large the
    grid — not O(slab / b_chunk) unrolled calls."""
    spec = P(mesh.axis_names, None)

    def one_chunk(args):
        # (events, v_end): the kernel's block step count is not kept here
        evt, v_end = ops.row_cycle_fused(*args, DT_NS, N_ACT_STEPS,
                                         N_RESTORE_STEPS, N_PRE_STEPS,
                                         backend=backend)
        return evt, v_end

    def device_fn(c, g, gc_res, gc_pre, v0, params):
        slab = c.shape[0]
        step = min(b_chunk, slab)
        args = (c, g, gc_res, gc_pre, v0, params)
        if step == slab:
            return one_chunk(args)
        chunked = tuple(x.reshape(slab // step, step, *x.shape[1:])
                        for x in args)
        evt, v_end = jax.lax.map(one_chunk, chunked)
        return (evt.reshape(slab, *evt.shape[2:]),
                v_end.reshape(slab, *v_end.shape[2:]))

    return jax.jit(jax.shard_map(device_fn, mesh=mesh,
                                 in_specs=(spec,) * 6,
                                 out_specs=(spec, spec), check_vma=False))


def row_cycle_fused_sharded(operands, sharding=None, backend: str = "auto",
                            b_chunk: int = transient.DEFAULT_B_CHUNK):
    """Sharded fused row-cycle dispatch -> (events (B, 4), v_end (B, N)).

    `operands` is a `FusedOperands` or the raw 6-tuple of kernel operand
    arrays; `sharding` is a Mesh / NamedSharding (None = all devices).
    Each device evaluates its own padded slab of the batch; the outputs
    are sliced back to the caller's B rows.
    """
    b_chunk = transient.validate_b_chunk(b_chunk)
    mesh = _as_mesh(sharding)
    sharding = sweep_sharding(mesh)
    n_dev = int(mesh.devices.size)
    core = list(operands[:6])
    b = core[0].shape[0]
    target = _dispatch_target(b, n_dev, b_chunk)
    padded = transient._pad_operands(core, target - b)
    padded = [put_global(x, sharding) for x in padded]
    evt, v_end = _sharded_engine(mesh, backend, b_chunk)(*padded)
    return evt[:b], v_end[:b]


def simulate_row_cycle_sharded(operands: FusedOperands, sharding=None,
                               backend: str = "auto",
                               b_chunk: int = transient.DEFAULT_B_CHUNK,
                               ) -> RowCycleResult:
    """Sharded twin of `transient.simulate_row_cycle_lowered`.

    Same lowered `FusedOperands` in, same trace-free `RowCycleResult`
    out — but the engine dispatch is distributed over the mesh instead of
    looping chunks on one device.  `dse.sweep(space, sharding=...)` calls
    this; the sequential path stays bit-identical and is the oracle.
    """
    contracts.check_operands(operands, where="shard.simulate_row_cycle_sharded")
    evt, _ = row_cycle_fused_sharded(operands, sharding, backend, b_chunk)
    return transient.result_from_events(operands, evt)


@functools.lru_cache(maxsize=None)
def _sharded_scorer(mesh: Mesh):
    """jit(shard_map(...)) of the sweep's rollup+score program, cached per
    mesh.  The body is `dse.score_from_events` — the IDENTICAL function
    the sequential `finalize_sweep` runs under a plain `jax.jit` — so the
    per-row arithmetic (and hence every scored column) is bit-identical;
    only the slab placement differs.  All per-row ops are elementwise, so
    no cross-device communication happens here at all."""
    from ..core import dse
    axis = mesh.axis_names
    in_specs = (P(axis), P(axis), P(axis), P(axis), P(axis, None))
    return jax.jit(jax.shard_map(dse.score_from_events, mesh=mesh,
                                 in_specs=in_specs, out_specs=P(axis),
                                 check_vma=False))


def _gather_columns(cols: dict, b: int) -> dict:
    """Slice scored column shards back to the caller's B rows.

    Fully-addressable results (single process, or a multi-process run
    dispatching on its local mesh): lazy slices of the sharded arrays —
    the only host-side materialization of the whole sweep, (B,) per
    column.  Results sharded across processes: every process needs the
    full columns to assemble an identical `DesignBatch`, so the
    addressable shards are allgathered first.
    """
    gathered = {}
    for k, v in cols.items():
        if not getattr(v, "is_fully_addressable", True):
            from jax.experimental import multihost_utils
            v = np.asarray(multihost_utils.process_allgather(v, tiled=True))
        gathered[k] = v[:b]
    return gathered


def sharded_sweep_columns(plan, sharding=None, backend: str = "auto",
                          b_chunk: int = transient.DEFAULT_B_CHUNK,
                          rows: tuple[int, int] | None = None) -> dict:
    """Device-side scored columns for a planned sweep -> dict of (B,) arrays.

    The end-to-end sharded pipeline of `dse.sweep(space, sharding=...)`:
    pad the plan's operand batch to identical per-device slabs, run the
    fused engine under `shard_map` (`_sharded_engine`), keep the raw
    event columns ON DEVICE as a sharded global array, and run the
    rollup+score program (`dse.score_from_events`) as a second sharded
    dispatch over the same slabs — no (B, N)-scale intermediate and no
    per-metric array ever materializes host-side.  Returns the
    `dse.score_columns` dict, sliced to the plan's design-point count,
    ready for `dse.assemble_batch`.

    `rows=(lo, hi)` restricts the dispatch to the design-point slab
    [lo, hi) — the elastic re-slabbing unit (`launch.elastic`): a slab's
    columns are computed on whatever mesh the survivors form, and
    concatenating slab columns in order reproduces the full-range result
    bit-identically (per-row arithmetic is slab-shape independent).
    On replica spaces the operand rows are the interleaved
    [replica, main] pairs of the point range (alignment is safe: every
    slab boundary is even, B_ALIGN being so).
    """
    from ..core.space import SpaceView
    b_chunk = transient.validate_b_chunk(b_chunk)
    mesh = _as_mesh(sharding)
    sharding = sweep_sharding(mesh)
    operands = plan.operands
    contracts.check_operands(operands, where="shard.sharded_sweep_columns")
    factor = 2 if operands.replica else 1
    view = SpaceView.from_lowered(plan.sp)
    cbl = jnp.asarray(plan.par.c_bl_total_ff, jnp.float32)
    sa_tau, overhead = operands.sa_tau_ns, operands.t_overhead_ns
    core = list(operands[:6])
    lo, hi = (0, len(view)) if rows is None else rows
    if not (0 <= lo <= hi <= len(view)):
        raise ValueError(f"rows={rows} outside the plan's design-point "
                         f"range [0, {len(view)})")
    if rows is not None:
        view = view.slice_rows(lo, hi)
        cbl = cbl[lo:hi]
        core = [x[factor * lo:factor * hi] for x in core]
        sa_tau = sa_tau[factor * lo:factor * hi]
        overhead = overhead[factor * lo:factor * hi]

    n_dev = int(mesh.devices.size)
    b_ops = core[0].shape[0]
    b_pts = hi - lo
    target_ops = _dispatch_target(b_ops, n_dev, b_chunk)
    pad_ops = target_ops - b_ops
    target_pts = target_ops // factor

    padded = transient._pad_operands(core, pad_ops)
    padded = [put_global(x, sharding) for x in padded]
    evt, _ = _sharded_engine(mesh, backend, b_chunk)(*padded)

    sa_tau = jnp.pad(sa_tau, (0, pad_ops), constant_values=1.0)
    overhead = jnp.pad(overhead, (0, pad_ops), constant_values=0.0)
    view = jax.tree.map(lambda x: put_global(x, sharding),
                        view.pad_to(target_pts))
    cbl = put_global(jnp.pad(cbl, (0, target_pts - b_pts),
                             constant_values=1.0), sharding)
    sa_tau = put_global(sa_tau, sharding)
    overhead = put_global(overhead, sharding)
    cols = _sharded_scorer(mesh)(view, cbl, sa_tau, overhead, evt)
    return _gather_columns(cols, b_pts)


@functools.lru_cache(maxsize=None)
def _sharded_pareto_engine(mesh: Mesh, block: int):
    """jit(shard_map(...)) of the Pareto dominance test, cached per
    (mesh, block).  Each device sweeps ITS dominator slab over the full
    (replicated) candidate batch in `block`-row sub-blocks — the exact
    masked-broadcast body of the sequential `dse.pareto_mask` loop — and
    the per-device dominated masks OR-reduce across the mesh.  Dominance
    is pure comparisons + boolean algebra (no rounding anywhere) and OR
    is commutative, so the reduced mask is bit-identical to the
    sequential block loop's."""
    axis = mesh.axis_names

    def device_fn(hi_d, lo_d, cand_d, hi, lo, cand):
        b = hi.shape[0]
        dominated = jnp.zeros((b,), bool)
        nloc = hi_d.shape[0]
        for i0 in range(0, nloc, block):   # dominator sub-blocks (static)
            hi_i, lo_i = hi_d[i0:i0 + block], lo_d[i0:i0 + block]
            cand_i = cand_d[i0:i0 + block]
            ge = ((hi_i[:, None, :] >= hi[None, :, :]).all(-1)
                  & (lo_i[:, None, :] <= lo[None, :, :]).all(-1))
            gt = ((hi_i[:, None, :] > hi[None, :, :]).any(-1)
                  | (lo_i[:, None, :] < lo[None, :, :]).any(-1))
            dominated |= (ge & gt & cand_i[:, None] & cand[None, :]).any(axis=0)
        return jax.lax.psum(dominated.astype(jnp.int32), axis) > 0

    in_specs = (P(axis, None), P(axis, None), P(axis), P(), P(), P())
    return jax.jit(jax.shard_map(device_fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=P(), check_vma=False))


def sharded_pareto_dominated(hi, lo, cand, sharding=None,
                             block: int = 4096) -> jnp.ndarray:
    """Sharded dominated-mask for `dse.pareto_mask` -> (B,) bool.

    `hi` / `lo` are the stacked (B, K) maximize/minimize objective
    columns and `cand` the (B,) candidate mask.  The dominator axis is
    padded to identical per-device slabs (padding rows carry cand=False,
    so they dominate nothing) and each device tests its slab against the
    full batch; a cross-device OR-reduce merges the verdicts.  NaN
    objectives compare False in every direction, so NaN rows neither
    dominate nor get spuriously dominated — exactly the sequential
    semantics.
    """
    mesh = _as_mesh(sharding)
    sharding = sweep_sharding(mesh)
    replicated = NamedSharding(mesh, P())
    n_dev = int(mesh.devices.size)
    hi = jnp.asarray(hi)
    lo = jnp.asarray(lo)
    cand = jnp.asarray(cand)
    b = int(hi.shape[0])
    pad = -(-b // n_dev) * n_dev - b
    hi_d = put_global(jnp.pad(hi, ((0, pad), (0, 0))), sharding)
    lo_d = put_global(jnp.pad(lo, ((0, pad), (0, 0))), sharding)
    cand_d = put_global(jnp.pad(cand, (0, pad)), sharding)
    full = [put_global(x, replicated) for x in (hi, lo, cand)]
    # out_specs=P() -> the mask comes back fully replicated, so it is
    # addressable (and identical) on every process — no gather needed.
    return _sharded_pareto_engine(mesh, int(block))(hi_d, lo_d, cand_d, *full)


def sharded_sweep(space=None, mesh=None, **sweep_kwargs):
    """`dse.sweep` over a device mesh (all local devices by default).

    Thin convenience wrapper:  `sharded_sweep(space)` ==
    `dse.sweep(space, sharding=make_sweep_mesh())`.
    """
    from ..core import dse
    return dse.sweep(space, sharding=sweep_sharding(mesh), **sweep_kwargs)


# ---------------------------------------------------------------------------
# Bit-equivalence smoke (tools/ci_check.sh runs this under forced devices)
# ---------------------------------------------------------------------------

def _equivalence_smoke(mc_samples: int = 16,
                       expect_devices: int | None = None) -> None:
    import time

    from ..core import dse
    from ..core.batch import ARRAY_FIELDS
    from ..core.space import DesignSpace

    mesh = make_sweep_mesh()
    n_dev = int(mesh.devices.size)
    if expect_devices is not None and n_dev != expect_devices:
        raise SystemExit(
            f"expected {expect_devices} devices but found {n_dev} — the "
            "forced host device count was lost (XLA_FLAGS must be set "
            "before the first jax import); a 1-device equivalence check "
            "would be near-tautological, refusing to fake an OK")

    def check(space, label):
        t0 = time.perf_counter()
        sharded = dse.sweep(space, sharding=mesh)
        dt = time.perf_counter() - t0
        seq = dse.sweep(space)
        bad = [f for f in ARRAY_FIELDS
               if not np.array_equal(np.asarray(getattr(sharded, f)),
                                     np.asarray(getattr(seq, f)))]
        bad += [f"corners[{k}]" for k in seq.corners
                if not np.array_equal(np.asarray(sharded.corners[k]),
                                      np.asarray(seq.corners[k]))]
        if bad:
            raise SystemExit(f"sharded sweep NOT bit-identical on {label}: "
                             f"mismatched fields {bad}")
        print(f"{label}: {len(seq)} points on {n_dev} device(s) in "
              f"{dt:.2f}s — bit-identical to the single-host sweep")

    check(DesignSpace.paper_grid(), "paper grid")
    check(DesignSpace.paper_grid().with_mc(samples=mc_samples, key=0),
          f"paper grid x {mc_samples} MC samples")
    check(DesignSpace.paper_targets().with_replica()
          .with_mc(samples=mc_samples, key=0),
          f"replica-closed targets x {mc_samples} MC samples")
    print("shard smoke: OK")


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="sharded-vs-single-host bit-equivalence check")
    parser.add_argument("--mc", type=int, default=16,
                        help="MC samples for the smoke's with_mc sweep")
    parser.add_argument("--expect-devices", type=int, default=None,
                        help="fail unless exactly this many devices are "
                             "visible (guards CI against losing the "
                             "forced host device count)")
    args = parser.parse_args()
    if args.smoke:
        _equivalence_smoke(mc_samples=args.mc,
                          expect_devices=args.expect_devices)
    else:
        parser.print_help()
