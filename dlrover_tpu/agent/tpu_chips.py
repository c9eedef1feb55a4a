"""One TPU chip per local worker (``--nproc_per_node`` > 1 on a TPU host).

A chip belongs to one process at a time, and libtpu hands a process every
chip on the host unless told otherwise — so a second worker would find
them taken. Each worker therefore gets its own chip and a place in the
host's process grid through libtpu's environment contract (the one JAX's
own multi-process test launcher uses): ``TPU_VISIBLE_CHIPS``,
``TPU_CHIPS_PER_PROCESS_BOUNDS``, ``TPU_PROCESS_BOUNDS``,
``TPU_PROCESS_ADDRESSES``, ``TPU_PROCESS_PORT``, ``CLOUD_TPU_TASK_ID``.

The agent never touches JAX: chips are counted from the PCI bus (the way
JAX itself decides whether a TPU is attached) and the device nodes.
"""

import glob
import os
from typing import Dict, Sequence

_GOOGLE_PCI_VENDOR_ID = "0x1ae0"
_TPU_PCI_DEVICE_IDS = frozenset({
    "0x0027",  # v3
    "0x0056",
    "0x005e",  # v4
    "0x0062",  # v5p
    "0x0063",  # v5e
    "0x006f",  # v6e
    "0x0076",
})
# Chips on the host -> TPU_PROCESS_BOUNDS with one chip per process. Only
# layouts that have run on hardware are listed (2x2: chip_smoke.py).
_PROCESS_BOUNDS = {4: "2,2,1"}


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return ""


def count_tpu_chips() -> int:
    """TPU chips this host's processes can open; 0 on a CPU-only machine.

    The PCI bus says whether TPUs are attached at all, but it can list
    chips this machine was not given; what libtpu can open are the device
    nodes (``/dev/accelN`` up to v4, one ``/dev/vfio/N`` group per chip
    since)."""
    attached = any(
        _read(vendor) == _GOOGLE_PCI_VENDOR_ID
        and _read(os.path.join(os.path.dirname(vendor), "device"))
        in _TPU_PCI_DEVICE_IDS
        for vendor in glob.glob("/sys/bus/pci/devices/*/vendor")
    )
    if not attached:
        return 0
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def check_layout(nproc_per_node: int, max_nodes: int, chips: int):
    """Refuse a worker layout the chip assignment cannot serve."""
    if chips == 0 or nproc_per_node == 1:
        return
    if max_nodes > 1:
        raise ValueError(
            f"--nproc_per_node={nproc_per_node} with --nnodes > 1 is not "
            "supported on TPU hosts: run one worker per host and let it "
            "drive all of the host's chips"
        )
    if nproc_per_node != chips or chips not in _PROCESS_BOUNDS:
        raise ValueError(
            f"--nproc_per_node={nproc_per_node} on a host with {chips} TPU "
            "chip(s): supported are 1 (one worker drives every chip) or "
            f"one worker per chip on a host with {sorted(_PROCESS_BOUNDS)} "
            "chips"
        )


def worker_chip_env(local_rank: int, ports: Sequence[int]) -> Dict[str, str]:
    """libtpu environment giving ``local_rank`` chip ``local_rank`` of a
    host with ``len(ports)`` chips; ``ports`` are the per-worker libtpu
    mesh ports, the same list for every worker."""
    return {
        "TPU_VISIBLE_CHIPS": str(local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _PROCESS_BOUNDS[len(ports)],
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{p}" for p in ports
        ),
        "TPU_PROCESS_PORT": str(ports[local_rank]),
        "CLOUD_TPU_TASK_ID": str(local_rank),
    }
