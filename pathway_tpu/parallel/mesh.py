"""Mesh construction and sharding helpers.

Axis convention: ``dp`` (data / corpus shards — maps to the reference's
worker shards, value.rs:38 low-bits key routing) and ``tp`` (tensor parallel
inside a model). A 1D dp mesh is the default; embedder tp is opt-in.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "dp"
TENSOR_AXIS = "tp"

def data_axis() -> str:
    return DATA_AXIS


def tensor_axis() -> str:
    return TENSOR_AXIS


def make_mesh(devices=None, dp: int | None = None, tp: int = 1) -> Mesh:
    """Build a (dp, tp) mesh over the given (default: all) devices."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if dp is None:
        dp = n // tp
    if dp * tp != n:
        raise ValueError(f"mesh {dp}x{tp} != {n} devices")
    arr = np.asarray(devices).reshape(dp, tp)
    return Mesh(arr, (DATA_AXIS, TENSOR_AXIS))


class MeshRef:
    """Hashable Mesh wrapper so a Mesh can be a jit static arg (shared by
    the sharded index kernels)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __hash__(self):
        return hash(
            (tuple(d.id for d in self.mesh.devices.flat),
             tuple(self.mesh.shape.items()))
        )

    def __eq__(self, other):
        return isinstance(other, MeshRef) and self.mesh == other.mesh


def local_mesh() -> Mesh:
    """1-chip degenerate mesh (one real TPU)."""
    return make_mesh(jax.devices()[:1], dp=1, tp=1)


def shard_batch(mesh: Mesh, *axes_rest: int) -> NamedSharding:
    """Sharding for an array whose leading dim is the batch (sharded on dp)."""
    spec = P(DATA_AXIS, *([None] * len(axes_rest)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---- serving mesh (PATHWAY_TPU_MESH) --------------------------------------
#
# The (dp, tp) mesh above serves the sharded index kernels. The
# PRODUCT serving path (continuous decoder server, embedder, in-query
# retrieval) runs on a three-axis ``(data, fsdp, tp)`` mesh instead:
# ``tp`` carries Megatron tensor parallelism (attention heads / ffn
# features / the KV pool's head axis), ``fsdp`` shards whatever ``tp``
# left replicated, and ``data`` is the replica/batch axis. Off — or on
# a 1x1x1 mesh — every annotation degenerates to single-chip placement,
# which is why `PATHWAY_TPU_MESH=0` is a byte-identical kill switch.

SERVE_DATA_AXIS = "data"
SERVE_FSDP_AXIS = "fsdp"
SERVE_TP_AXIS = "tp"
SERVE_AXES = (SERVE_DATA_AXIS, SERVE_FSDP_AXIS, SERVE_TP_AXIS)


class MeshShapeError(ValueError):
    """An impossible serving-mesh shape, raised on the HOST at mesh
    construction — before any array is placed — instead of surfacing as
    an opaque XLA sharding crash mid-dispatch. Carries the requested
    axis lengths and the device count for the error report."""

    def __init__(self, msg: str, *, data: int, fsdp: int, tp: int,
                 n_devices: int):
        super().__init__(
            f"{msg} (requested data={data} fsdp={fsdp} tp={tp} over "
            f"{n_devices} devices)"
        )
        self.data = data
        self.fsdp = fsdp
        self.tp = tp
        self.n_devices = n_devices


def make_serving_mesh(devices=None, *, data: int = 1, fsdp: int = 1,
                      tp: int = 0) -> Mesh:
    """Build the ``(data, fsdp, tp)`` serving mesh over the given
    (default: all) devices. ``tp=0`` means auto: every device left over
    after ``data * fsdp``. Impossible shapes raise
    :class:`MeshShapeError` (typed, host-side) rather than letting XLA
    crash on a malformed device assignment."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    data, fsdp, tp = int(data), int(fsdp), int(tp)
    if data < 1 or fsdp < 1 or tp < 0:
        raise MeshShapeError(
            "serving-mesh axis lengths must be positive",
            data=data, fsdp=fsdp, tp=tp, n_devices=n,
        )
    if tp == 0:
        if n % (data * fsdp) != 0:
            raise MeshShapeError(
                f"data*fsdp={data * fsdp} does not divide the device "
                "count, so tp cannot be inferred",
                data=data, fsdp=fsdp, tp=tp, n_devices=n,
            )
        tp = n // (data * fsdp)
    if data * fsdp * tp != n:
        raise MeshShapeError(
            f"data*fsdp*tp={data * fsdp * tp} != device count",
            data=data, fsdp=fsdp, tp=tp, n_devices=n,
        )
    arr = np.asarray(devices).reshape(data, fsdp, tp)
    return Mesh(arr, SERVE_AXES)


def serving_mesh_from_flags(devices=None) -> Mesh | None:
    """The serving mesh `PATHWAY_TPU_MESH{,_DATA,_FSDP,_TP}` asks for,
    or ``None`` with the kill switch off. Flags are read per call (the
    continuous server reads ONCE at construction, like every other
    serving knob)."""
    from pathway_tpu.internals.config import pathway_config

    if not pathway_config.mesh:
        return None
    return make_serving_mesh(
        devices,
        data=pathway_config.mesh_data,
        fsdp=pathway_config.mesh_fsdp,
        tp=pathway_config.mesh_tp,
    )


def mesh_is_trivial(mesh: Mesh | None) -> bool:
    """True when ``mesh`` is None or spans a single device — the regime
    where every NamedSharding degenerates to plain placement and the
    byte-identity pin applies."""
    return mesh is None or mesh.devices.size == 1


def spec_with_fsdp(spec: P, shape: tuple, fsdp: int,
                   axis: str = SERVE_FSDP_AXIS) -> P:
    """Overlay the ``fsdp`` axis onto ``spec``'s first unsharded dim
    whose length it divides (ZeRO-3-style remainder sharding). With
    ``fsdp == 1`` — or no divisible dim — the spec is returned
    unchanged, so the annotation can never force padding."""
    if fsdp <= 1:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, d) in enumerate(zip(parts, shape)):
        if p is None and d % fsdp == 0 and d > 0:
            parts[i] = axis
            return P(*parts)
    return spec


def spec_dropping_nondividing(spec: P, shape: tuple, mesh: Mesh) -> P:
    """``spec`` with every mesh axis removed from dims it does not
    divide evenly (those dims degrade to replicated). Lenient-placement
    companion to the strict ``validate_*_mesh`` checks: modules with no
    ``shard_map`` seam (pure-GSPMD encoders) shard what divides and
    replicate the rest instead of refusing the mesh."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for p, d in zip(parts, shape):
        if p is None:
            out.append(None)
            continue
        axes = (p,) if isinstance(p, str) else tuple(p)
        size = 1
        for a in axes:
            size *= int(mesh.shape.get(a, 1))
        out.append(p if size > 0 and d % size == 0 else None)
    return P(*out)


def place_pytree(tree, mesh: Mesh | None, specs=None):
    """``jax.device_put`` every array leaf of ``tree`` with the
    ``NamedSharding`` its entry in ``specs`` (a matching pytree of
    ``PartitionSpec`` / None) names — replicated where the spec is
    missing. ``mesh=None`` returns the tree untouched (single-chip
    path)."""
    if mesh is None:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if specs is None:
        spec_leaves = [P()] * len(leaves)
    else:
        spec_leaves = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: x is None or isinstance(x, P)
        )[0]
    placed = [
        jax.device_put(leaf, NamedSharding(mesh, spec if spec is not None
                                           else P()))
        for leaf, spec in zip(leaves, spec_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, placed)
