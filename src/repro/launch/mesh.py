"""Production mesh construction (TPU v5e) and the instance Layout type.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True, order=True)
class Layout:
    """A parallelism layout for one serving instance: ``sp`` sequence-
    parallel shards x ``tp`` tensor-parallel shards, ``degree = sp * tp``
    devices per replica.  ``Layout(1, tp)`` is the classic pure-TP
    configuration; ``Layout(2, 2)`` is the SP2xTP2 layout the scheduler
    prefers for long-context decode (LoongServe-style elastic sequence
    parallelism: each sp shard attends over its slice of the page table
    and the partial softmax states combine across the ``sp`` axis).

    The layout — not the TP degree alone — is the unit of
    transformation: an engine moves TP4 <-> SP2xTP2 live through the
    same ``TransformSession`` machinery that changes degrees."""
    sp: int = 1
    tp: int = 1

    def __post_init__(self):
        if self.sp < 1 or self.tp < 1:
            raise ValueError(f"layout factors must be >= 1: {self}")

    @property
    def degree(self) -> int:
        """Devices per replica: ``sp * tp``."""
        return self.sp * self.tp

    @staticmethod
    def of(value) -> "Layout":
        """Coerce an int TP degree (the legacy call shape) or a Layout."""
        if isinstance(value, Layout):
            return value
        return Layout(1, int(value))

    def __str__(self) -> str:
        return (f"SP{self.sp}xTP{self.tp}" if self.sp > 1
                else f"TP{self.tp}")


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the sharding rules here are
    ``with_sharding_constraint`` hints that GSPMD propagates, which
    Explicit axes (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int = 8):
    """A single host's instance-group mesh (Gyges transformation scope)."""
    return make_mesh((n,), ("worker",))


def make_instance_mesh(devices, layout):
    """The transformable instance-group mesh: W devices re-factorized as
    ``(rep, sp, tp)`` with ``rep * sp * tp == W``.  Every layout of the
    same device list reuses one PartitionSpec tree (core/instance.py) —
    a parallelism transformation is re-factorizing this mesh and
    resharding live arrays to it.  ``layout`` is a ``Layout`` or a bare
    int TP degree (the legacy call shape, ``sp=1``)."""
    import numpy as np

    lay = Layout.of(layout)
    W = len(devices)
    if W % lay.degree:
        raise ValueError(f"layout {lay} (degree {lay.degree}) does not "
                         f"divide {W} devices")
    dev = np.asarray(devices).reshape(W // lay.degree, lay.sp, lay.tp)
    return jax.sharding.Mesh(dev, ("rep", "sp", "tp"))


def batch_axes(mesh) -> tuple:
    """Axes a batch dimension shards over (pod+data when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]


def data_axis_size(mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n
