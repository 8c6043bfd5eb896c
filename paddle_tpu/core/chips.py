"""One process per chip: the check a parent runs before it spawns
children that will open the accelerator.

A TPU chip belongs to one process at a time, and a process that opens the
TPU backend claims every chip of its host (nothing here pins a child to
one device yet — ROADMAP B7). So on a host with an accelerator:

* a parent that has initialised the backend itself holds the chips, and
  any child that needs them fails or hangs;
* more than one unpinned child is a race for the same chips.

Both used to surface as a hung or crashed child. ``check_spawn`` turns
them into a typed error in the parent, before anything is launched.
Children whose environment pins ``JAX_PLATFORMS=cpu`` (the test suite,
CPU rehearsals) are never limited. N replicas on one accelerator host
run in ONE process today: ``ClusterController(inprocess=True)``.
"""

from __future__ import annotations

import glob
import sys
from typing import Mapping


class ChipContentionError(RuntimeError):
    """Spawning these children would put more than one process on the
    host's accelerator chips."""


def local_chip_count() -> int:
    """Accelerator chips of this host, counted from the TPU driver's
    device nodes — WITHOUT touching JAX, which would claim them."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def children_use_cpu(env: Mapping[str, str]) -> bool:
    first = env.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    return first == "cpu"


def parent_holds_chip() -> bool:
    """True once THIS process has initialised a non-CPU JAX backend."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() != "cpu"


def check_spawn(n_children: int, env: Mapping[str, str], what: str) -> None:
    """Raise ChipContentionError if launching ``n_children`` processes
    with environment ``env`` would contend for this host's chips."""
    if children_use_cpu(env):
        return
    chips = local_chip_count()
    if chips == 0:
        return          # no accelerator here: the children land on the CPU
    if parent_holds_chip():
        raise ChipContentionError(
            f"{what}: this process has initialised the accelerator "
            f"backend and holds the host's {chips} chip(s); a child that "
            f"needs them would fail or hang. Spawn before touching JAX, "
            f"or pin the children to JAX_PLATFORMS=cpu")
    if n_children > 1:
        raise ChipContentionError(
            f"{what}: {n_children} unpinned processes on a host with "
            f"{chips} chip(s) — each would claim every chip (no device "
            f"pinning yet). Run one process per host, or the in-process "
            f"backend (inprocess=True) for N replicas")
