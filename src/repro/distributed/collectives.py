"""Strapped hierarchical collectives (the paper's Selector+Strap, on a mesh).

The pod boundary is the HCB interface: few, expensive links.  In-pod ICI is
the local strap.  A gradient all-reduce therefore runs as:

  1. reduce-scatter over the in-pod "data" axis   (strap-local aggregation)
  2. all-reduce of the 1/N shard over "pod"       (one bond per strap),
     optionally int8-compressed with a shared scale + error feedback
  3. all-gather back over "data"

Cross-pod bytes drop by |data| (x4 more with int8), exactly like C_BL when
the selector keeps unselected straps off the global line.

These run inside `shard_map`; `hierarchical_psum_tree` is the user-facing
gradient synchronizer (used by the DP train loop and the perf experiments).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _pad_to(x, mult: int):
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, n


def _psum_int8(x, axis_name: str):
    """Cross-pod all-reduce of an int8-quantized tensor with a pod-agreed
    scale.  Returns the dequantized sum and the local quantization error
    (for error feedback)."""
    absmax = jnp.max(jnp.abs(x))
    scale = jax.lax.pmax(absmax, axis_name) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    err = x - q.astype(jnp.float32) * scale
    total = jax.lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale, err


def strapped_psum(x, data_axis: str = "data", pod_axis: str | None = "pod",
                  compress: bool = False):
    """Hierarchical psum of one flat array inside shard_map.

    Returns (summed x, error_feedback or None)."""
    nd = jax.lax.psum(1, data_axis)
    flat = x.reshape(-1)
    flat, n = _pad_to(flat, nd)
    # 1. strap-local reduce-scatter
    shard = jax.lax.psum_scatter(flat, data_axis, scatter_dimension=0,
                                 tiled=True)
    err = None
    if pod_axis is not None:
        # 2. one bond per strap crosses the pod boundary
        if compress:
            shard, err = _psum_int8(shard, pod_axis)
        else:
            shard = jax.lax.psum(shard, pod_axis)
    # 3. strap-local all-gather
    full = jax.lax.all_gather(shard, data_axis, axis=0, tiled=True)
    out = full[:n].reshape(x.shape)
    if err is not None:
        err_full = jax.lax.all_gather(err, data_axis, axis=0, tiled=True)
        err = err_full[:n].reshape(x.shape)
    return out, err


def hierarchical_psum_tree(grads, mesh: Mesh, compress: bool = False,
                           mean: bool = True):
    """Synchronize a replicated gradient pytree across ("pod","data").

    Gradients enter per-device (each device holds its local-batch gradient)
    and leave identical on all devices.  Returns (grads, error_feedback)."""
    has_pod = "pod" in mesh.axis_names
    pod_axis = "pod" if has_pod else None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # gradients are reduced over the DP axes only (model shards hold
    # different parameter shards and never mix)
    n_total = sizes.get("data", 1) * sizes.get("pod", 1)

    def inner(*leaves):
        outs, errs = [], []
        for leaf in leaves:
            s, e = strapped_psum(leaf.astype(jnp.float32), "data", pod_axis,
                                 compress)
            if mean:
                s = s / n_total
            outs.append(s)
            errs.append(e if e is not None else jnp.zeros_like(s))
        return tuple(outs) + tuple(errs)

    leaves, treedef = jax.tree.flatten(grads)
    spec = P()  # every leaf fully replicated; shard_map sees local copies
    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=tuple(spec for _ in leaves),
                       out_specs=tuple(spec for _ in range(2 * len(leaves))),
                       check_vma=False)
    results = fn(*leaves)
    outs = jax.tree.unflatten(treedef, results[: len(leaves)])
    errs = jax.tree.unflatten(treedef, results[len(leaves):])
    return outs, errs


def collective_matrix(mesh: Mesh) -> dict:
    """Bandwidth bookkeeping for the roofline: bytes crossing each axis for
    a hierarchical vs flat all-reduce of G bytes on this mesh."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    nd = sizes.get("data", 1)
    npod = sizes.get("pod", 1)
    flat_cross_pod = 2.0 * (npod - 1) / npod         # ring AR fraction
    strapped_cross_pod = flat_cross_pod / nd          # shard is 1/nd
    return dict(axes=sizes,
                flat_cross_pod_bytes_per_byte=flat_cross_pod,
                strapped_cross_pod_bytes_per_byte=strapped_cross_pod,
                strap_factor=nd)
