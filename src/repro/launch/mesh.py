"""Production mesh + axis rules.

Single pod: 16×16 = 256 chips, axes (data, model) — data parallelism over
rows, tensor/expert/context parallelism over columns (the TPU v5e 2-D torus
maps one torus dim per mesh axis, matching DFModel's one-network-dim-per-
strategy assumption). Multi-pod: 2×16×16, the 'pod' axis is outer data
parallelism over the inter-pod DCN/ICI links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..parallel.logical import AxisRules


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """A device mesh whose axes are all ``AxisType.Auto``.

    The model code places work through GSPMD: logical-axis rules and
    ``with_sharding_constraint``, which may only name Auto axes.
    ``jax.make_mesh`` builds Explicit axes by default, so every mesh in the
    repo is built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def make_axis_rules(mesh: Mesh, cfg=None,
                    kv_replicate: bool = False) -> AxisRules:
    """Logical→mesh axis mapping for the production layout.

    'seq' is unsharded for training (per-device full sequences);
    'kv_seq' (decode KV cache) shards on 'model' — context parallelism.

    ``kv_replicate`` (§Perf knob): when GQA kv heads do not divide the
    model axis (e.g. kv=8 on a 16-wide axis), GSPMD's 8→16 resharding
    forces involuntary full rematerializations of K/V; replicating the
    (small) K/V projections instead removes those copies.
    """
    ba = batch_axes(mesh)
    kv = "model"
    if kv_replicate:
        kv = None
    return AxisRules({
        "batch": ba,
        "seq": None,
        "heads": "model",
        "kv_heads": kv,
        "ff": "model",
        "vocab": "model",
        "experts": "model",
        "kv_seq": "model",
    })


def safe_spec(shape: tuple[int, ...], spec: P, mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim (e.g. batch=1
    long-context cells can't shard batch) — GSPMD would reject them."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis_size(ax):
        if ax is None:
            return 1
        if isinstance(ax, (tuple, list)):
            out = 1
            for a in ax:
                out *= sizes[a]
            return out
        return sizes[ax]

    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        fixed.append(ax if ax is not None and dim % axis_size(ax) == 0
                     else None)
    return P(*fixed)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)
