"""FC1xx — dispatch auditing on the compiled artifact.

The repo's core invariant — every design corner flows through ONE fused
row-cycle evaluation — is enforced here on the *compiled* form, not the
source text.  Each entry-point config in `ENTRY_CONFIGS` executes a real
public code path (`dse.sweep`, `plan_sweep`+`row_cycle_events`+
`finalize_sweep`, `simulate_row_cycle_many`, the sharded `launch/shard`
driver, the micro-batching `DSEService` window, replica and `with_mc`
variants) under a dispatch recorder, then the distinct engine shape
buckets it exercised are traced/compiled and audited:

- **FC101** — the entry point issued a different number of fused engine
  dispatches than its contract declares (a second dispatch sneaking into
  a "one fused evaluation" path, or a fan-out that stopped chunking).
- **FC102** — a host callback / host transfer primitive inside the
  jitted dispatch region (jaxpr callback primitives, HLO infeed/outfeed
  and non-allowlisted custom-calls): silent device<->host sync on every
  sweep.
- **FC103** — silent f64 promotion in the dispatch (jaxpr eqn avals or
  `f64[` in compiled HLO): doubles bandwidth on an engine calibrated in
  f32.
- **FC104** — an oversized folded constant baked into the dispatch
  (closed-jaxpr consts or HLO `constant(...)` instructions above
  `CONST_BYTES_LIMIT`): operand data leaking into the compiled artifact
  makes every distinct value a fresh compile.
- **FC105** — the dispatch group does not lower to exactly ONE
  `pallas_call` when traced with `backend="pallas"` (trace-only, so the
  audit runs on CPU too).

Requires jax + the repro package importable; the CLI adds `src/` to
`sys.path`.  All jax imports are function-local so `--list-rules` and
the stdlib-only locks analyzer never pay them.
"""

from __future__ import annotations

import contextlib
import dataclasses

from .common import Finding

RULES = {
    "FC101": "entry point issued an unexpected number of fused dispatches",
    "FC102": "host callback / host transfer inside the jitted dispatch",
    "FC103": "silent f64 promotion in the fused dispatch",
    "FC104": "oversized folded constant baked into the dispatch",
    "FC105": "dispatch group does not lower to exactly one pallas_call",
}

# one folded constant bigger than this is operand data, not a parameter
CONST_BYTES_LIMIT = 128 * 1024

# jaxpr primitives that call back into Python / transfer to host
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "infeed", "outfeed",
    "device_put",
})

# custom-call targets XLA:CPU/TPU legitimately emits for the fused engine
# (none today: the engine is pure lax/while lowering; extend deliberately)
CUSTOM_CALL_ALLOWLIST = frozenset()


@dataclasses.dataclass(frozen=True)
class EngineCall:
    """One concrete fused-engine invocation seen by the recorder."""
    shapes: tuple
    dtypes: tuple
    statics: tuple      # (dt, n_act, n_res, n_pre, backend)

    @property
    def key(self) -> tuple:
        return (self.shapes, self.dtypes, self.statics)


class DispatchRecorder:
    """Counts fused-engine and sharded-engine dispatches while patched in."""

    def __init__(self):
        self.engine_calls: list[EngineCall] = []
        self.sharded_calls: list[tuple] = []
        self.scorer_calls: list[tuple] = []   # device-side rollup+score
        self.pareto_calls: list[tuple] = []   # sharded dominance engine
        self.orig_engine = None      # unpatched ops.row_cycle_fused

    @property
    def total(self) -> int:
        return (len(self.engine_calls) + len(self.sharded_calls)
                + len(self.scorer_calls) + len(self.pareto_calls))


@contextlib.contextmanager
def record_dispatches():
    """Patch the two dispatch seams and yield a `DispatchRecorder`.

    Seams: `ops.row_cycle_fused` (every sequential/chunked/serving path
    funnels through this module attribute) and the three lru-cached
    jit(shard_map) engines of `launch.shard` — `_sharded_engine` (fused
    kernel), `_sharded_scorer` (device-side rollup+score) and
    `_sharded_pareto_engine` (distributed dominance) — whose per-call
    wrappers count invocations even when the cached engine is reused.
    Tracer-valued calls — the sharded engine re-entering the patched op
    during its own trace — are not dispatches and are skipped.  The
    patched op returns the engine's own `RowCycleOut` untouched: events,
    v_end and the kernel's block step count (`.block_steps`), which the
    chunk loop sums into its `engine.dispatch` counters.
    """
    import jax

    from repro.kernels import ops
    from repro.launch import shard

    rec = DispatchRecorder()
    orig = ops.row_cycle_fused
    rec.orig_engine = orig

    def counted(c, g, gc_res, gc_pre, v0, params, dt, n_act, n_res, n_pre,
                backend="auto"):
        if not isinstance(c, jax.core.Tracer):
            arrays = (c, g, gc_res, gc_pre, v0, params)
            rec.engine_calls.append(EngineCall(
                shapes=tuple(tuple(x.shape) for x in arrays),
                dtypes=tuple(str(x.dtype) for x in arrays),
                statics=(float(dt), int(n_act), int(n_res), int(n_pre),
                         str(backend))))
        return orig(c, g, gc_res, gc_pre, v0, params, dt, n_act, n_res,
                    n_pre, backend=backend)

    orig_sharded = shard._sharded_engine
    orig_scorer = shard._sharded_scorer
    orig_pareto = shard._sharded_pareto_engine

    def counted_sharded(mesh, backend, b_chunk):
        inner = orig_sharded(mesh, backend, b_chunk)

        def run(*args):
            rec.sharded_calls.append(
                (tuple(mesh.shape.items()), str(backend), int(b_chunk)))
            return inner(*args)
        return run

    def counted_scorer(mesh):
        inner = orig_scorer(mesh)

        def run(*args):
            rec.scorer_calls.append((tuple(mesh.shape.items()),))
            return inner(*args)
        return run

    def counted_pareto(mesh, block):
        inner = orig_pareto(mesh, block)

        def run(*args):
            rec.pareto_calls.append((tuple(mesh.shape.items()), int(block)))
            return inner(*args)
        return run

    ops.row_cycle_fused = counted
    shard._sharded_engine = counted_sharded
    shard._sharded_scorer = counted_scorer
    shard._sharded_pareto_engine = counted_pareto
    try:
        yield rec
    finally:
        ops.row_cycle_fused = orig
        shard._sharded_engine = orig_sharded
        shard._sharded_scorer = orig_scorer
        shard._sharded_pareto_engine = orig_pareto


# ---------------------------------------------------------------------------
# Entry-point configs: name -> runner(recorder) -> expected dispatch count
# ---------------------------------------------------------------------------

def _chunk_dispatches(n_rows: int, b_chunk: int) -> int:
    """Dispatch count of `_row_cycle_fused_chunked` for an n_rows batch."""
    if n_rows <= b_chunk:
        return 1
    return -(-n_rows // b_chunk)


def _run_sweep_targets(rec):
    from repro.core import dse
    from repro.core.space import DesignSpace
    dse.sweep(DesignSpace.paper_targets())
    return 1


def _run_sweep_paper_grid(rec):
    from repro.core import dse
    from repro.core.space import DesignSpace
    dse.sweep(DesignSpace.paper_grid())
    return 1


def _run_sweep_mc(rec):
    from repro.core import dse
    from repro.core.space import DesignSpace
    dse.sweep(DesignSpace.paper_targets().with_mc(samples=8, key=0))
    return 1


def _run_sweep_replica(rec):
    from repro.core import dse
    from repro.core.space import DesignSpace
    dse.sweep(DesignSpace.paper_targets().with_replica())
    return 1


def _run_sweep_replica_mc(rec):
    from repro.core import dse
    from repro.core.space import DesignSpace
    dse.sweep(DesignSpace.paper_targets().with_replica()
              .with_mc(samples=8, key=0))
    return 1


def _run_sweep_chunked(rec):
    """paper grid through b_chunk=64: the chunk loop must fan out to
    exactly ceil(padded/64) dispatches — no more (double dispatch), no
    fewer (silent chunk merge past the caller's memory bound)."""
    from repro.core import dse
    from repro.core.space import DesignSpace
    space = DesignSpace.paper_grid()
    plan = dse.plan_sweep(space)
    n = int(plan.operands.c.shape[0])
    dse.sweep(space, b_chunk=64)
    padded = -(-n // 64) * 64
    return _chunk_dispatches(padded if n > 64 else n, 64)


def _run_events_seam(rec):
    """The serving seam by hand: plan -> row_cycle_events -> rollup ->
    finalize, exactly one engine dispatch."""
    from repro.core import dse, transient
    from repro.core.space import DesignSpace
    plan = dse.plan_sweep(DesignSpace.paper_targets())
    evt = transient.row_cycle_events(plan.operands)
    res = transient.result_from_events(plan.operands, evt)
    dse.finalize_sweep(plan, res)
    return 1


def _run_many_entries(rec):
    """simulate_row_cycle_many over a 2-entry combo list: one flattened
    batch, one dispatch — never one per combo."""
    import jax.numpy as jnp
    from repro.core import transient
    from repro.core.calibration import TECHS
    tech = next(iter(TECHS.values()))
    layers = jnp.asarray([32.0, 64.0])
    transient.simulate_row_cycle_many(
        [(tech, "sel_strap", layers), (tech, "direct", layers)])
    return 1


def _run_service_window(rec):
    """One DSEService micro-batch window over 3 queries (2 distinct + 1
    coalesced duplicate), all nominal: one packed slab, one dispatch."""
    from repro.core.space import DesignSpace
    from repro.serving.dse_service import DSEService
    svc = DSEService(memo_entries=0)
    s_a = DesignSpace.paper_targets()
    s_b = DesignSpace.paper_grid()
    futs = [svc.submit(s_a), svc.submit(s_b), svc.submit(s_a)]
    svc.flush()
    for f in futs:
        f.result(timeout=60)
    return 1


def _run_service_mixed_replica(rec):
    """A window mixing nominal and replica queries: the packer groups by
    replica mode, so exactly TWO dispatches — one per group."""
    from repro.core.space import DesignSpace
    from repro.serving.dse_service import DSEService
    svc = DSEService(memo_entries=0)
    s_a = DesignSpace.paper_targets()
    futs = [svc.submit(s_a), svc.submit(s_a.with_replica())]
    svc.flush()
    for f in futs:
        f.result(timeout=60)
    return 2


def _run_sharded(rec):
    """Full sharded fabric: one engine dispatch + one device-side scorer
    dispatch for the sweep, then one sharded dominance dispatch for the
    Pareto mask — exactly three, never a host-side fallback."""
    from repro.core import dse
    from repro.core.space import DesignSpace
    from repro.launch.mesh import make_sweep_mesh
    mesh = make_sweep_mesh()
    batch = dse.sweep(DesignSpace.paper_targets(), sharding=mesh)
    dse.pareto_mask(batch, sharding=mesh)
    return 3


def _run_legacy_params5(rec):
    """Direct engine call with the legacy 5-column params layout (no role
    column) — still one dispatch, and its bucket is audited like any
    other."""
    from repro.core import dse, transient
    from repro.core.space import DesignSpace
    from repro.kernels import ops
    plan = dse.plan_sweep(DesignSpace.paper_targets())
    core = transient._pad_operands(
        plan.operands[:6],
        (-int(plan.operands.c.shape[0])) % transient.B_ALIGN)
    c, g, gc_res, gc_pre, v0, params = [x[:transient.B_ALIGN] for x in core]
    ops.row_cycle_fused(c, g, gc_res, gc_pre, v0, params[:, :5],
                        transient.DT_NS, transient.N_ACT_STEPS,
                        transient.N_RESTORE_STEPS, transient.N_PRE_STEPS,
                        backend="ref")
    return 1


ENTRY_CONFIGS = (
    ("sweep-targets", _run_sweep_targets),
    ("sweep-paper-grid", _run_sweep_paper_grid),
    ("sweep-mc", _run_sweep_mc),
    ("sweep-replica", _run_sweep_replica),
    ("sweep-replica-mc", _run_sweep_replica_mc),
    ("sweep-chunked-64", _run_sweep_chunked),
    ("events-seam", _run_events_seam),
    ("many-entries", _run_many_entries),
    ("service-window", _run_service_window),
    ("service-mixed-replica", _run_service_mixed_replica),
    ("sharded-default-mesh", _run_sharded),
    ("legacy-params5", _run_legacy_params5),
)


# ---------------------------------------------------------------------------
# Bucket analysis: jaxpr + compiled-HLO invariants per distinct shape bucket
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Every eqn in a jaxpr, recursing into sub-jaxprs in eqn params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            yield from _iter_param_eqns(v)


def _iter_param_eqns(v):
    inner = getattr(v, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        yield from _iter_eqns(inner)
    elif hasattr(v, "eqns"):
        yield from _iter_eqns(v)
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _iter_param_eqns(x)


def _bucket_name(call: EngineCall) -> str:
    b, n = call.shapes[0]
    return (f"B{b}xN{n}/params{call.shapes[5][1]}"
            f"/backend={call.statics[4]}")


def analyze_bucket(call: EngineCall, engine_fn=None):
    """Audit one engine shape bucket: trace (pallas + dispatched backend)
    and compile (dispatched backend) the engine over ShapeDtypeStructs,
    then check FC102-FC105.  Yields `Finding`s."""
    import jax
    import numpy as np

    from repro.kernels import ops
    from repro.roofline import hlo as hlomod

    fn = engine_fn if engine_fn is not None else ops.row_cycle_fused
    where = _bucket_name(call)
    args = [jax.ShapeDtypeStruct(s, d)
            for s, d in zip(call.shapes, call.dtypes)]
    dt, n_act, n_res, n_pre, backend = call.statics

    def traced(bk):
        return jax.make_jaxpr(
            lambda *a: fn(*a, dt, n_act, n_res, n_pre, backend=bk))(*args)

    # FC105: the pallas lowering of this bucket must be ONE kernel launch
    closed_p = traced("pallas")
    n_pallas = sum(1 for eqn in _iter_eqns(closed_p.jaxpr)
                   if eqn.primitive.name == "pallas_call")
    if n_pallas != 1:
        yield Finding(
            "FC105", where, 0, 0,
            f"backend='pallas' trace contains {n_pallas} pallas_call "
            "primitives; the fused engine must lower to exactly ONE "
            "kernel launch per dispatch group", key="pallas-count")

    # FC102/FC103/FC104 on the backend this bucket actually dispatched
    closed = traced(backend)
    prims = {eqn.primitive.name for eqn in _iter_eqns(closed.jaxpr)}
    callbacks = sorted(prims & CALLBACK_PRIMITIVES)
    if callbacks:
        yield Finding(
            "FC102", where, 0, 0,
            f"jaxpr contains host callback/transfer primitive(s) "
            f"{callbacks} inside the jitted dispatch region",
            key="jaxpr-callback")
    f64_eqns = sorted({
        eqn.primitive.name for eqn in _iter_eqns(closed.jaxpr)
        for var in eqn.outvars
        if str(getattr(getattr(var, "aval", None), "dtype", "")) == "float64"
    })
    if f64_eqns:
        yield Finding(
            "FC103", where, 0, 0,
            f"jaxpr eqn(s) {f64_eqns} produce float64 values — silent "
            "f64 promotion in an f32-calibrated engine", key="jaxpr-f64")
    big_consts = [(int(np.asarray(c).nbytes), type(c).__name__)
                  for c in closed.consts
                  if hasattr(c, "shape")
                  and int(np.asarray(c).nbytes) > CONST_BYTES_LIMIT]
    if big_consts:
        yield Finding(
            "FC104", where, 0, 0,
            f"closed jaxpr folds {len(big_consts)} constant(s) over "
            f"{CONST_BYTES_LIMIT} bytes (largest "
            f"{max(b for b, _ in big_consts)}); operand data baked into "
            "the trace recompiles per value", key="jaxpr-const")

    hlo_text = jax.jit(
        lambda *a: fn(*a, dt, n_act, n_res, n_pre, backend=backend)
    ).lower(*args).compile().as_text()
    bad_calls = {t: n for t, n in
                 hlomod.scan_custom_call_targets(hlo_text).items()
                 if t not in CUSTOM_CALL_ALLOWLIST}
    host_ops = hlomod.scan_host_transfer_ops(hlo_text)
    if bad_calls or host_ops:
        yield Finding(
            "FC102", where, 0, 0,
            f"compiled HLO contains host-interaction ops: custom-calls "
            f"{sorted(bad_calls)} / host transfers {sorted(host_ops)}",
            key="hlo-host")
    f64_lines = hlomod.scan_f64_mentions(hlo_text, limit=3)
    if f64_lines:
        yield Finding(
            "FC103", where, 0, 0,
            f"compiled HLO mentions f64 shapes, e.g. {f64_lines[0][:120]}",
            key="hlo-f64")
    big = hlomod.scan_constant_bytes(hlo_text, min_bytes=CONST_BYTES_LIMIT + 1)
    if big:
        yield Finding(
            "FC104", where, 0, 0,
            f"compiled HLO holds {len(big)} constant instruction(s) over "
            f"{CONST_BYTES_LIMIT} bytes (largest {big[0][0]})",
            key="hlo-const")


def audit_dispatch(configs=None, engine_fn=None):
    """Run every entry-point config, then audit the distinct shape
    buckets.  Returns (findings_with_line_text, stats_dict); line text is
    always "" (config findings fingerprint on their stable `key`).

    `configs` / `engine_fn` exist for the seeded-violation self-tests:
    a config may issue an extra dispatch, and `engine_fn` substitutes the
    traced engine (e.g. one that launches two pallas kernels).
    """
    configs = ENTRY_CONFIGS if configs is None else tuple(configs)
    findings = []
    buckets: dict[tuple, EngineCall] = {}
    per_config = {}
    for name, runner in configs:
        with record_dispatches() as rec:
            expected = runner(rec)
        per_config[name] = {"expected": expected, "actual": rec.total,
                            "sharded": len(rec.sharded_calls),
                            "scorer": len(rec.scorer_calls),
                            "pareto": len(rec.pareto_calls)}
        if rec.total != expected:
            findings.append(Finding(
                "FC101", name, 0, 0,
                f"entry point issued {rec.total} fused dispatch(es) "
                f"(engine {len(rec.engine_calls)} + sharded "
                f"{len(rec.sharded_calls)} + scorer "
                f"{len(rec.scorer_calls)} + pareto "
                f"{len(rec.pareto_calls)}), contract says {expected}",
                key="dispatch-count"))
        for call in rec.engine_calls:
            buckets.setdefault(call.key, call)
    for call in buckets.values():
        findings.extend(analyze_bucket(call, engine_fn=engine_fn))
    stats = {
        "configs": per_config,
        "buckets_analyzed": [_bucket_name(c) for c in buckets.values()],
    }
    return [(f, "") for f in findings], stats


# ---------------------------------------------------------------------------
# Seeded violations (self-test / --seed-violation): prove the gate fails
# ---------------------------------------------------------------------------

def _run_seeded_double_dispatch(rec):
    """Dispatches the targets sweep TWICE while declaring one — FC101."""
    from repro.core import dse
    from repro.core.space import DesignSpace
    space = DesignSpace.paper_targets()
    dse.sweep(space)
    dse.sweep(space)
    return 1


def seeded_double_pallas_engine(c, g, gc_res, gc_pre, v0, params, dt,
                                n_act, n_res, n_pre, backend="auto"):
    """An engine whose dispatch group launches TWO kernels — FC105."""
    from repro.kernels import ops
    from repro.kernels.ref import RowCycleOut
    out = ops.row_cycle_fused(c, g, gc_res, gc_pre, v0, params, dt,
                              n_act, n_res, n_pre, backend=backend)
    evt2, _ = ops.row_cycle_fused(c, g, gc_res, gc_pre, v0, params, dt,
                                  n_act, n_res, n_pre, backend=backend)
    return RowCycleOut(out[0] + 0 * evt2, out[1], out.block_steps)


SEEDED_CONFIGS = {
    "extra-dispatch": (("seeded-extra-dispatch",
                        _run_seeded_double_dispatch),),
}
