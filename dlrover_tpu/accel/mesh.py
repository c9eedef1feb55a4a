"""Device-mesh construction — the ``create_parallel_group`` analog.

Parity: reference ``atorch/atorch/distributed/distributed.py:320``
(``create_parallel_group(([(name,size)...], rank_order))`` builds one torch
process group per named dim). On TPU there are no process groups: ONE
``jax.sharding.Mesh`` carries every named axis, and XLA lowers collectives
onto the ICI torus (intra-slice) or DCN (inter-slice) from sharding
annotations alone.

Axis order convention (outermost first): ``data`` and ``fsdp`` outermost —
their collectives (gradient/param all-reduce-scatter) tolerate DCN latency —
then ``pipe``, ``seq``, ``expert``, with ``tensor`` innermost so its
per-layer all-gathers ride the fastest ICI dimension. This is the standard
mesh layout from the scaling-book recipe.
"""

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from dlrover_tpu.common.log import logger

# Canonical axis order, outermost (slowest, DCN-tolerant) to innermost
# (fastest ICI). Matches the reference's rank_order semantics
# (distributed.py:263 _get_pg_ranks) re-keyed for ICI locality.
AXIS_ORDER = ("data", "fsdp", "pipe", "seq", "expert", "tensor")


@dataclass
class MeshConfig:
    """Named axes with sizes; -1 means "absorb remaining devices"."""

    axes: List[Tuple[str, int]] = field(default_factory=list)

    def resolved(self, n_devices: int) -> List[Tuple[str, int]]:
        sizes = dict(self.axes)
        known = 1
        wildcard = None
        for name, size in self.axes:
            if size == -1:
                if wildcard is not None:
                    raise ValueError("at most one axis may be -1")
                wildcard = name
            else:
                known *= size
        if wildcard is not None:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by {known}"
                )
            sizes[wildcard] = n_devices // known
            known *= sizes[wildcard]
        if known != n_devices:
            raise ValueError(
                f"mesh axes {dict(self.axes)} use {known} devices, have "
                f"{n_devices}"
            )
        return [(name, sizes[name]) for name, _ in self.axes]


def _canonical_order(axes: Sequence[Tuple[str, int]]) -> List[Tuple[str, int]]:
    known = [a for a in axes if a[0] in AXIS_ORDER]
    extra = [a for a in axes if a[0] not in AXIS_ORDER]
    return sorted(known, key=lambda a: AXIS_ORDER.index(a[0])) + extra


def create_mesh(axes: Sequence[Tuple[str, int]],
                devices: Optional[Sequence] = None,
                reorder: bool = True):
    """Build a ``jax.sharding.Mesh`` from named (axis, size) dims.

    ``devices`` defaults to all devices; sizes may contain one ``-1``
    wildcard. With ``reorder=True`` axes are put in the canonical
    ICI-locality order (see AXIS_ORDER) regardless of argument order, so
    callers can say ``[("tensor", 4), ("data", -1)]`` without thinking
    about torus layout.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    resolved = MeshConfig(list(axes)).resolved(len(devices))
    if reorder:
        resolved = _canonical_order(resolved)
    names = tuple(n for n, _ in resolved)
    shape = tuple(s for _, s in resolved)
    try:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True
        )
    except (ValueError, AssertionError) as e:
        # A partial slice (survivors of a kill) has no torus assignment;
        # enumeration order is always valid, but say so on a real chip:
        # the axes may then not ride ICI neighbours.
        if devices[0].platform == "tpu":
            logger.warning(
                "create_device_mesh%s failed (%s); using device "
                "enumeration order", shape, e,
            )
        dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, names)
    logger.info("created mesh %s", dict(zip(names, shape)))
    return mesh


def local_mesh(axis: str = "data"):
    """A 1-axis mesh over this process's addressable devices (debug/tests)."""
    import jax

    return create_mesh([(axis, -1)], devices=jax.local_devices(),
                       reorder=False)
