"""Study specs, made from a configuration, a traffic mix and a seed.

A spec is plain data (JSON-able) that both sides read: `design_space`
turns it into the program's `DesignSpace`, and `reference.study_columns`
computes the same study from its own equations.

    {"entries": [[tech, scheme, [layers...]], ...],
     "corners": [],
     "mc": {"samples": S, "key": k, "corr": c} | None,
     "replica": bool}

`corners` holds corner axes, `[[axis, [values...]], ...]`, for the
reference; no committed mix sweeps them, so it is empty.
"""

from __future__ import annotations

# MC key of study i of a run with seed s: (s << STUDY_KEY_BITS) + i, so
# every study of a window draws its own Monte-Carlo samples.
STUDY_KEY_BITS = 10


def grid_entries(config) -> list:
    """The configuration's design grid as [tech, scheme, layers] entries."""
    return [[tech, scheme, [float(n) for n in layers]]
            for tech, schemes, layers in config["grid"] for scheme in schemes]


def study_spec(config, mix, seed: int, index: int) -> dict:
    """Spec of study `index` of a run: the whole grid, and Monte-Carlo
    samples keyed by the seed and the study's index."""
    mc = None
    if config.get("mc") and mix.get("mc_samples"):
        mc = {"samples": int(mix["mc_samples"]),
              "key": (int(seed) << STUDY_KEY_BITS) + int(index),
              "corr": float(config["mc"]["corr"])}
    return {"entries": grid_entries(config), "corners": [], "mc": mc,
            "replica": bool(config.get("replica"))}


def design_space(spec):
    """The program's `DesignSpace` for a spec."""
    from repro.core.space import DesignSpace
    space = DesignSpace.points([(t, s, tuple(layers)) for t, s, layers in spec["entries"]])
    if spec.get("mc"):
        mc = spec["mc"]
        space = space.with_mc(samples=mc["samples"], key=mc["key"], corr=mc["corr"])
    return space.with_replica(bool(spec.get("replica")))
