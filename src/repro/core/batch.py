"""Structure-of-arrays design batches — the result half of the DSE API.

A `DesignBatch` holds every scored metric of a design-space sweep as one
flat jnp array per field (plus a validity mask), registered as a JAX
pytree: it `jit`s, `tree_map`s, and shards.  The batch axis is the ONLY
axis, so distributing a million-point sweep is literally

    batch = jax.device_put(batch, NamedSharding(mesh, P("batch")))

(or `batch.device_put(sharding)`), after `pad_to()`-aligning the axis to
the device count.  `to_points()` is the thin legacy view producing the old
`list[DesignPoint]` contract.

Monte-Carlo sweeps (`DesignSpace.with_mc`) keep the SAME flat layout:
sample s of base design i sits at row `s * base_len + i`, and the batch
records `n_samples` / `base_len` as static aux data.  The yield views
(`yield_fraction`, `quantile`, `mc_summary`) are masked segment
reductions over that flat axis — no second array axis ever appears, so
jit/tree_map/sharding semantics are unchanged.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass, replace

import numpy as np
import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class DesignPoint:
    """Scalar view of one design point.

    Deprecated as a bulk interface: `dse.sweep` returns a `DesignBatch`,
    and list-of-points consumers should migrate to its array fields.
    `DesignBatch.to_points()` keeps this contract alive in the meantime.
    """
    tech: str
    scheme: str
    layers: int
    density_gb_mm2: float
    height_um: float
    cbl_ff: float
    margin_mv: float
    margin_disturbed_mv: float
    trc_ns: float
    e_write_fj: float
    e_read_fj: float
    hcb_pitch_um: float
    blsa_area_um2: float
    feasible: bool


# Array leaves of the pytree, in flatten order.  All shaped (B,) on the
# single shardable batch axis.
ARRAY_FIELDS = (
    "tech_idx", "scheme_idx", "layers",
    "density_gb_mm2", "height_um", "cbl_ff",
    "margin_mv", "margin_disturbed_mv",
    "trc_ns", "t_sense_ns", "t_fire_ns", "margin_fire_mv",
    "e_write_fj", "e_read_fj",
    "hcb_pitch_um", "blsa_area_um2",
    "manufacturable", "feasible", "valid",
)

# Columns a with_mc sweep actually perturbs (per-sample SA offset enters
# the margins; the Vth draw enters the access conductance, hence timing).
MC_SAMPLED_FIELDS = ("margin_mv", "margin_disturbed_mv",
                     "trc_ns", "t_sense_ns", "t_fire_ns", "margin_fire_mv")


def batches_identical(a, b) -> bool:
    """NaN-aware bit-identity over every array field + corner channel."""

    def eq(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return False
        if x.dtype.kind == "f":
            return bool(((x == y) | (np.isnan(x) & np.isnan(y))).all())
        return bool((x == y).all())

    return (set(a.corners) == set(b.corners)
            and all(eq(getattr(a, f), getattr(b, f)) for f in ARRAY_FIELDS)
            and all(eq(a.corners[k], b.corners[k]) for k in a.corners))


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class DesignBatch:
    """One design-space sweep as a structure-of-arrays pytree.

    `tech_idx`/`scheme_idx` index the static `tech_names`/`scheme_names`
    tables (pytree aux data, so they survive jit/flatten round-trips
    without becoming tracers).  `valid` masks padding rows added by
    `pad_to`; every reduction in the DSE layer respects it.
    """

    tech_idx: jnp.ndarray            # (B,) int32 into tech_names
    scheme_idx: jnp.ndarray          # (B,) int32 into scheme_names
    layers: jnp.ndarray              # (B,) float32
    density_gb_mm2: jnp.ndarray      # (B,) float32
    height_um: jnp.ndarray           # (B,) float32
    cbl_ff: jnp.ndarray              # (B,) float32
    margin_mv: jnp.ndarray           # (B,) float32
    margin_disturbed_mv: jnp.ndarray # (B,) float32
    trc_ns: jnp.ndarray              # (B,) float32 (NaN when transient off)
    t_sense_ns: jnp.ndarray          # (B,) float32 (NaN when transient off)
    t_fire_ns: jnp.ndarray           # (B,) float32 SA-enable fire time
    #                                  (replica-closed when the space
    #                                  declared with_replica; NaN when the
    #                                  transient is off or timing never
    #                                  closed)
    margin_fire_mv: jnp.ndarray      # (B,) float32 sense margin at the
    #                                  actual SA fire (dv at fire - offset)
    e_write_fj: jnp.ndarray          # (B,) float32
    e_read_fj: jnp.ndarray           # (B,) float32
    hcb_pitch_um: jnp.ndarray        # (B,) float32
    blsa_area_um2: jnp.ndarray       # (B,) float32
    manufacturable: jnp.ndarray      # (B,) bool
    feasible: jnp.ndarray            # (B,) bool
    valid: jnp.ndarray               # (B,) bool
    corners: dict                    # axis name -> (B,) float32
    tech_names: tuple = ()           # static lookup tables (aux data)
    scheme_names: tuple = ()
    n_samples: int = 1               # MC sample fan-out (1 = nominal sweep)
    base_len: int = 0                # design points per sample (0 = len)

    # ------------------------------------------------------------ pytree --
    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in ARRAY_FIELDS)
        children += (self.corners,)
        return children, (self.tech_names, self.scheme_names,
                          self.n_samples, self.base_len)

    @classmethod
    def tree_unflatten(cls, aux, children):
        tech_names, scheme_names, n_samples, base_len = aux
        kwargs = dict(zip(ARRAY_FIELDS, children[:-1]))
        return cls(corners=children[-1], tech_names=tech_names,
                   scheme_names=scheme_names, n_samples=n_samples,
                   base_len=base_len, **kwargs)

    # ------------------------------------------------------------- shape --
    def __len__(self) -> int:
        return int(self.tech_idx.shape[0])

    @property
    def n_valid(self) -> int:
        return int(np.asarray(self.valid).sum())

    @property
    def tech_col(self) -> list:
        """Per-row tech names (host-side convenience)."""
        return [self.tech_names[i] for i in np.asarray(self.tech_idx)]  # repro-lint: disable=RL002  (host-side report view, not sweep-path compute)

    @property
    def scheme_col(self) -> list:
        """Per-row scheme names (host-side convenience)."""
        return [self.scheme_names[i] for i in np.asarray(self.scheme_idx)]  # repro-lint: disable=RL002  (host-side report view, not sweep-path compute)

    def select(self, where) -> "DesignBatch":
        """Rows selected by a boolean mask or index array (host-side).

        Selecting rows of a Monte-Carlo batch destroys the sample-major
        layout the MC reductions assume, so the MC aux is cleared to a
        sentinel (`n_samples=0`): stale `yield_fraction`/`quantile`/
        `mc_summary` calls on the selection raise instead of silently
        reducing a broken layout.  Reduce first (`mc_summary`) and select
        the per-design summary instead.
        """
        idx = np.asarray(where)
        if idx.dtype == bool:
            idx = np.flatnonzero(idx)
        take = lambda a: jnp.asarray(a)[idx]
        out = jax.tree_util.tree_map(take, self)
        return replace(out, n_samples=0 if self.n_samples != 1 else 1,
                       base_len=0)

    def slice_rows(self, start: int, stop: int) -> "DesignBatch":
        """Contiguous row slice [start:stop) — the demux/streaming helper.

        Cheaper and more explicit than `select` for the serving layer's
        per-client slab slices and per-chunk streaming: no index
        materialization, plain array slicing on every leaf.  Like
        `select`, slicing a Monte-Carlo batch destroys the sample-major
        layout, so the MC aux is cleared to the `n_samples=0` sentinel
        unless the batch was a plain (n_samples == 1) sweep.
        """
        start, stop = int(start), int(stop)
        if not 0 <= start <= stop <= len(self):
            raise ValueError(
                f"slice_rows [{start}:{stop}) out of range for a "
                f"{len(self)}-row batch")
        cut = lambda a: jnp.asarray(a)[start:stop]
        out = jax.tree_util.tree_map(cut, self)
        return replace(out, n_samples=0 if self.n_samples != 1 else 1,
                       base_len=0)

    @classmethod
    def concat(cls, batches) -> "DesignBatch":
        """Merge batches row-wise into one flat batch — the micro-batch
        packing helper.

        Name tables are unioned (indices remapped per input batch), so
        batches from different sweeps compose.  All inputs must carry the
        same corner channels and be plain (n_samples == 1) batches —
        concatenating sample-major MC layouts would interleave segments
        of different bases, so MC batches must be `mc_summary`-reduced
        first.
        """
        batches = list(batches)
        if not batches:
            raise ValueError("concat needs at least one batch")
        corner_keys = set(batches[0].corners)
        for b in batches[1:]:
            if set(b.corners) != corner_keys:
                raise ValueError(
                    "concat needs identical corner channels on every "
                    f"batch (got {sorted(corner_keys)} vs "
                    f"{sorted(b.corners)})")
        if any(b.n_samples != 1 for b in batches):
            raise ValueError(
                "concat only composes plain (n_samples == 1) batches; "
                "reduce MC batches with mc_summary first — concatenating "
                "sample-major layouts would interleave their segments")
        tech_names: list = []
        scheme_names: list = []
        for b in batches:
            for n in b.tech_names:
                if n not in tech_names:
                    tech_names.append(n)
            for n in b.scheme_names:
                if n not in scheme_names:
                    scheme_names.append(n)
        parts = []
        for b in batches:
            tmap = np.asarray([tech_names.index(n) for n in b.tech_names]
                              or [0], np.int32)
            smap = np.asarray([scheme_names.index(n) for n in b.scheme_names]
                              or [0], np.int32)
            parts.append(replace(
                b,
                tech_idx=jnp.asarray(tmap)[b.tech_idx],
                scheme_idx=jnp.asarray(smap)[b.scheme_idx]))
        # field-wise concatenation (NOT tree_map: the inputs' static aux
        # data — name tables — legitimately differ before the union)
        cat = lambda xs: jnp.concatenate([jnp.asarray(x) for x in xs])
        kwargs = {f: cat([getattr(p, f) for p in parts])
                  for f in ARRAY_FIELDS}
        corners = {k: cat([p.corners[k] for p in parts])
                   for k in batches[0].corners}
        return cls(corners=corners, tech_names=tuple(tech_names),
                   scheme_names=tuple(scheme_names),
                   n_samples=1, base_len=0, **kwargs)

    def pad_to(self, multiple: int) -> "DesignBatch":
        """Pad the batch axis up to a multiple (sharding/chunk alignment).

        Padding rows have `valid=False` and zeros elsewhere; every DSE
        reduction and `to_points()` ignores them.
        """
        b = len(self)
        pad = (-b) % multiple
        if not pad:
            return self
        def padarr(a):
            a = jnp.asarray(a)
            return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return jax.tree_util.tree_map(padarr, self)

    def device_put(self, sharding) -> "DesignBatch":
        """Place every leaf with the given jax.sharding / device."""
        return jax.device_put(self, sharding)

    # -------------------------------------------------- Monte-Carlo views --
    # Sample-major layout contract (dse.sweep on a with_mc space): sample s
    # of base design i is flat row `s * base_len + i`; pad_to may append
    # invalid rows at the end.  Every reduction below is a masked segment
    # reduction over the flat batch axis — `select()`ed batches lose the
    # layout and are rejected.
    #
    # Importance sampling: a space lowered with a shifted/scaled tail
    # proposal (`with_mc(..., tail_shift=, tail_scale=)`) carries per-row
    # log-weights in `corners["mc_log_w"]`; every reduction consumes them
    # automatically (self-normalized estimators).  Without the channel the
    # weights are uniform and each reduction takes the ORIGINAL unweighted
    # code path — bit-identical to the plain i.i.d. estimators.

    def _mc_base(self) -> int:
        if self.n_samples == 0:
            raise ValueError(
                "MC reductions need the sweep's sample-major layout, which "
                "select() destroys — reduce first (mc_summary) and select "
                "the per-design summary batch instead")
        base = self.base_len or len(self)
        if len(self) < self.n_samples * base:
            raise ValueError(
                "MC reductions need the sweep's sample-major layout "
                f"({self.n_samples} samples x {base} designs), but the "
                f"batch has only {len(self)} rows — was it select()ed?")
        return base

    def _mc_weights(self) -> jnp.ndarray | None:
        """Per-row importance weights from the reserved `mc_log_w`
        channel, max-stabilized and zeroed on invalid rows — or None when
        the batch carries no weights (uniform; reductions then take the
        original unweighted code path bit-for-bit)."""
        log_w = self.corners.get("mc_log_w")
        if log_w is None:
            return None
        log_w = jnp.where(self.valid, jnp.asarray(log_w, jnp.float32),
                          -jnp.inf)
        peak = jnp.max(log_w)
        peak = jnp.where(jnp.isfinite(peak), peak, 0.0)
        return jnp.exp(log_w - peak)        # exp(-inf) == 0 on invalid rows

    def _segment_frac(self, ok: jnp.ndarray, base: int,
                      weights: jnp.ndarray | None = None) -> jnp.ndarray:
        ids = jnp.arange(len(self)) % base
        # A design with ZERO valid samples (or zero total weight) has no
        # yield estimate at all: NaN, not 0.0, so never-evaluated designs
        # cannot masquerade as true yield-0 designs (pareto_mask's NaN
        # columns neither dominate nor get dominated, so they pass
        # through selection unharmed).
        if weights is None:
            hits = jax.ops.segment_sum((ok & self.valid).astype(jnp.float32),
                                       ids, num_segments=base)
            tot = jax.ops.segment_sum(self.valid.astype(jnp.float32),
                                      ids, num_segments=base)
            return jnp.where(tot > 0.0, hits / jnp.maximum(tot, 1.0),
                             jnp.nan)
        hits = jax.ops.segment_sum(weights * (ok & self.valid), ids,
                                   num_segments=base)
        tot = jax.ops.segment_sum(weights, ids, num_segments=base)
        return jnp.where(tot > 0.0,
                         hits / jnp.where(tot > 0.0, tot, 1.0), jnp.nan)

    def _spec_ok(self, margin_mv: float | None, trc_ns: float | None,
                 disturbed: bool) -> jnp.ndarray:
        """Per-row spec pass mask (folded with validity)."""
        ok = self.valid
        if margin_mv is not None:
            col = self.margin_disturbed_mv if disturbed else self.margin_mv
            ok = ok & (col >= margin_mv)
        if trc_ns is not None:
            ok = ok & (self.trc_ns <= trc_ns)
        return ok

    def yield_fraction(self, margin_mv: float | None = None,
                       trc_ns: float | None = None,
                       disturbed: bool = False) -> jnp.ndarray:
        """Per-design fraction of MC samples meeting the spec -> (base,).

        A sample passes when its sense margin is at least `margin_mv`
        (the disturbed margin when `disturbed=True`) AND its row-cycle
        time is at most `trc_ns`; criteria passed as None are skipped.
        NaN tRC (a `with_transient=False` sweep) never passes a tRC spec.
        On a nominal sweep (no `with_mc`) this is a 0/1 pass map.  A
        design whose samples are ALL invalid has no estimate and yields
        NaN (distinct from true yield 0).  On an importance-sampled batch
        this is the self-normalized weighted estimate.
        """
        base = self._mc_base()
        return self._segment_frac(self._spec_ok(margin_mv, trc_ns,
                                                disturbed),
                                  base, self._mc_weights())

    def quantile(self, q, field: str = "trc_ns") -> jnp.ndarray:
        """Per-design quantile of a metric across MC samples -> (base,)
        (or (len(q), base) for a vector `q`).  Invalid rows are ignored.
        On an importance-sampled batch the quantile is read off the
        weighted empirical CDF (invalid/NaN rows carry zero weight)."""
        base = self._mc_base()
        n = self.n_samples * base
        vals = jnp.asarray(getattr(self, field), jnp.float32)[:n]
        weights = self._mc_weights()
        if weights is None:
            vals = jnp.where(self.valid[:n], vals, jnp.nan)
            return jnp.nanquantile(vals.reshape(self.n_samples, base),
                                   jnp.asarray(q), axis=0)
        vals = vals.reshape(self.n_samples, base)
        w = weights[:n].reshape(self.n_samples, base)
        # a row is a CDF knot only when valid AND finite: invalid rows
        # carry stale values (their weight is already zero, but leaving
        # the value in the sort would anchor low-q interpolation to it)
        usable = jnp.isfinite(vals) & self.valid[:n].reshape(
            self.n_samples, base)
        w = jnp.where(usable, w, 0.0)
        sortkey = jnp.where(usable, vals, jnp.inf)
        order = jnp.argsort(sortkey, axis=0)
        v = jnp.take_along_axis(sortkey, order, axis=0)
        ww = jnp.take_along_axis(w, order, axis=0)
        tot = ww.sum(axis=0)
        # clamp the +inf sentinel rows to the column's largest usable
        # value so interpolation beyond the last weighted point saturates
        vmax = jnp.max(jnp.where(usable & (w > 0.0), vals, -jnp.inf),
                       axis=0)
        v = jnp.where(jnp.isfinite(v), v, vmax[None, :])
        midpts = (jnp.cumsum(ww, axis=0) - 0.5 * ww)
        cdf = midpts / jnp.maximum(tot, 1e-30)[None, :]
        q_arr = jnp.asarray(q, jnp.float32)
        qs = jnp.atleast_1d(q_arr)
        out = jax.vmap(lambda p, vv: jnp.interp(qs, p, vv),
                       in_axes=(1, 1), out_axes=1)(cdf, v)
        out = jnp.where(tot[None, :] > 0.0, out, jnp.nan)
        return out[0] if q_arr.ndim == 0 else out

    def ess(self) -> jnp.ndarray:
        """Per-design effective sample size (Kish) -> (base,).

        `(sum w)^2 / sum w^2` over each design's valid samples — the
        diagnostic for how much an importance-sampled estimate can be
        trusted.  Uniform weights reduce it to the valid-sample count."""
        base = self._mc_base()
        w = self._mc_weights()
        if w is None:
            w = self.valid.astype(jnp.float32)
        ids = jnp.arange(len(self)) % base
        s1 = jax.ops.segment_sum(w, ids, num_segments=base)
        s2 = jax.ops.segment_sum(w * w, ids, num_segments=base)
        return jnp.where(s2 > 0.0,
                         s1 * s1 / jnp.where(s2 > 0.0, s2, 1.0), 0.0)

    def yield_ppm(self, margin_mv: float | None = None,
                  trc_ns: float | None = None, disturbed: bool = False,
                  z_conf: float = 1.959964, min_ess: float = 8.0) -> dict:
        """Deep-tail spec-FAILURE estimate per design, in parts per
        million -> dict of (base,) arrays.

        Unlike the self-normalized bulk reductions, this is the
        *unnormalized* importance-sampling estimator — the standardized
        draws have a known (unit) normalizing constant, so
        `p = (1/N) sum_i w_i [fail_i]` with the exact density-ratio
        weights.  Weights only ever multiply failure indicators, which is
        what makes ppm tails tractable: under a proposal shifted into the
        failure region the weights ON that region are uniformly small and
        well-behaved, where a self-normalized estimate would be drowned
        by the bulk samples' huge weights.

            fail_ppm            point estimate, failures per million
            fail_ppm_lo/hi      `z_conf`-sigma normal-approximation CI
                                bounds (clipped to [0, 1e6])
            ess                 per-design *tail* effective sample size:
                                `(sum w f)^2 / sum (w f)^2`, the
                                effective number of independent failure
                                observations behind the estimate

        A design whose tail ESS is below `min_ess` — too few (effective)
        observed failures, including the zero-observed-failure case — or
        with zero valid samples reports NaN: no estimate, mirroring
        `yield_fraction`'s zero-valid-sample NaN semantics, never a fake
        0 ppm.
        """
        base = self._mc_base()
        ok = self._spec_ok(margin_mv, trc_ns, disturbed)
        fail = (self.valid & ~ok).astype(jnp.float32)
        log_w = self.corners.get("mc_log_w")
        if log_w is None:
            wf = fail
        else:
            w = jnp.exp(jnp.asarray(log_w, jnp.float32))
            wf = jnp.where(self.valid, w, 0.0) * fail
        ids = jnp.arange(len(self)) % base
        n = jax.ops.segment_sum(self.valid.astype(jnp.float32), ids,
                                num_segments=base)
        n_safe = jnp.maximum(n, 1.0)
        s1 = jax.ops.segment_sum(wf, ids, num_segments=base)
        s2 = jax.ops.segment_sum(wf * wf, ids, num_segments=base)
        p_fail = s1 / n_safe
        # unnormalized-IS variance:  Var(w f) / N
        var = jnp.maximum(s2 / n_safe - p_fail * p_fail, 0.0) / n_safe
        sd = jnp.sqrt(var)
        ess = jnp.where(s2 > 0.0,
                        s1 * s1 / jnp.where(s2 > 0.0, s2, 1.0), 0.0)
        good = (n > 0.0) & (ess >= min_ess)
        to_ppm = lambda p: jnp.clip(p, 0.0, 1.0) * 1e6
        nan = jnp.nan
        return {
            "fail_ppm": jnp.where(good, to_ppm(p_fail), nan),
            "fail_ppm_lo": jnp.where(good, to_ppm(p_fail - z_conf * sd),
                                     nan),
            "fail_ppm_hi": jnp.where(good, to_ppm(p_fail + z_conf * sd),
                                     nan),
            "ess": ess,
        }

    def mc_summary(self, margin_mv: float | None = None,
                   trc_ns: float | None = None, disturbed: bool = False,
                   q: float = 0.5,
                   min_feasible_frac: float = 0.5) -> "DesignBatch":
        """Reduce an MC batch to one row per base design.

        Sampled metrics (`margin_mv`, `margin_disturbed_mv`, `trc_ns`,
        `t_sense_ns`) collapse to their per-design `q`-quantile;
        deterministic columns take the first sample's value.  `feasible`
        becomes "at least `min_feasible_frac` of samples feasible", and
        `corners["yield_frac"]` records `yield_fraction(margin_mv,
        trc_ns, disturbed)` — ready to use as a Pareto/selection
        objective (`dse.pareto_front(..., extra_maximize=...)`,
        `dse.best_design(..., min_yield=...)`).

        On an importance-sampled batch every reduced column (yield,
        quantiles, feasible fraction) is the weighted estimate, and
        `corners["ess"]` carries the per-design effective sample size
        diagnostic.  The raw `mc_*` draw/weight channels never survive
        the reduction.
        """
        base = self._mc_base()
        yf = self.yield_fraction(margin_mv=margin_mv, trc_ns=trc_ns,
                                 disturbed=disturbed)
        take = lambda a: jnp.asarray(a)[:base]
        kwargs = {f: take(getattr(self, f)) for f in ARRAY_FIELDS}
        for f in MC_SAMPLED_FIELDS:
            kwargs[f] = self.quantile(q, f).astype(jnp.float32)
        feas_frac = self._segment_frac(self.feasible, base,
                                       self._mc_weights())
        kwargs["feasible"] = ((feas_frac >= min_feasible_frac)
                              & kwargs["valid"])
        corners = {k: take(v) for k, v in self.corners.items()
                   if not k.startswith("mc_")}
        corners["yield_frac"] = yf.astype(jnp.float32)
        corners["ess"] = self.ess().astype(jnp.float32)
        return DesignBatch(corners=corners, tech_names=self.tech_names,
                           scheme_names=self.scheme_names, **kwargs)

    # ------------------------------------------------------ legacy views --
    def point(self, i: int) -> DesignPoint:
        """Scalar `DesignPoint` view of row `i`."""
        col = lambda f: np.asarray(getattr(self, f))[i]
        return DesignPoint(
            tech=self.tech_names[int(col("tech_idx"))],
            scheme=self.scheme_names[int(col("scheme_idx"))],
            layers=int(col("layers")),
            density_gb_mm2=float(col("density_gb_mm2")),
            height_um=float(col("height_um")),
            cbl_ff=float(col("cbl_ff")),
            margin_mv=float(col("margin_mv")),
            margin_disturbed_mv=float(col("margin_disturbed_mv")),
            trc_ns=float(col("trc_ns")),
            e_write_fj=float(col("e_write_fj")),
            e_read_fj=float(col("e_read_fj")),
            hcb_pitch_um=float(col("hcb_pitch_um")),
            blsa_area_um2=float(col("blsa_area_um2")),
            feasible=bool(col("feasible")))

    def to_points(self) -> list:
        """Deprecated compatibility view: the old `list[DesignPoint]`
        contract of `full_sweep`.  Skips invalid (padding) rows.  New code
        should consume the array fields directly.  Removal timeline:
        docs/api.md."""
        warnings.warn(
            "DesignBatch.to_points is deprecated and will be removed (see "
            "docs/api.md for the timeline); consume the DesignBatch array "
            "columns directly (tech_col/scheme_col for names, point(i) "
            "for a single row)",
            DeprecationWarning, stacklevel=2)
        valid = np.asarray(self.valid)
        return [self.point(i) for i in np.flatnonzero(valid)]  # repro-lint: disable=RL002  (deprecated per-point export shim; sweep path is array-native)

    @classmethod
    def from_points(cls, points) -> "DesignBatch":
        """Build a batch from legacy `DesignPoint`s (or anything with the
        same attributes); the bridge for list-based callers.

        `DesignPoint` does not record manufacturability (only the combined
        `feasible` verdict), so the bridged `manufacturable` column is a
        placeholder (all True) — consume it only on batches produced by
        `dse.sweep`.  `t_sense_ns` is likewise NaN here."""
        points = list(points)
        tech_names: list = []
        scheme_names: list = []
        for p in points:
            if p.tech not in tech_names:
                tech_names.append(p.tech)
            if p.scheme not in scheme_names:
                scheme_names.append(p.scheme)
        f32 = lambda f: jnp.asarray([getattr(p, f) for p in points],
                                    jnp.float32)
        b = len(points)
        return cls(
            tech_idx=jnp.asarray([tech_names.index(p.tech) for p in points],
                                 jnp.int32),
            scheme_idx=jnp.asarray(
                [scheme_names.index(p.scheme) for p in points], jnp.int32),
            layers=f32("layers"),
            density_gb_mm2=f32("density_gb_mm2"), height_um=f32("height_um"),
            cbl_ff=f32("cbl_ff"), margin_mv=f32("margin_mv"),
            margin_disturbed_mv=f32("margin_disturbed_mv"),
            trc_ns=f32("trc_ns"),
            t_sense_ns=jnp.full((b,), jnp.nan, jnp.float32),
            t_fire_ns=jnp.full((b,), jnp.nan, jnp.float32),
            margin_fire_mv=jnp.full((b,), jnp.nan, jnp.float32),
            e_write_fj=f32("e_write_fj"), e_read_fj=f32("e_read_fj"),
            hcb_pitch_um=f32("hcb_pitch_um"),
            blsa_area_um2=f32("blsa_area_um2"),
            manufacturable=jnp.ones((b,), bool),   # not in DesignPoint
            feasible=jnp.asarray([bool(p.feasible) for p in points], bool),
            valid=jnp.ones((b,), bool),
            corners={},
            tech_names=tuple(tech_names), scheme_names=tuple(scheme_names))
