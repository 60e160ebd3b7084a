"""``host_offload`` residency: spill stashed activations to host DRAM.

The SlimPipe-style alternative to BPipe's partner swap: instead of
shipping the newest held unit to the paired *device*, OFFLOAD copies it
to host memory over the D2H link and FETCH copies it back ahead of the
backward. Same spill discipline (``policy.spill``), same cap formulas —
what changes is the link: host bandwidth (PCIe-class) instead of
NVLink/ICI, which is exactly the trade the simulator prices
(``SimConfig.d2h_bw/h2d_bw``) and the planner searches.

In the executor the copy is real: ``jax.vjp``'s returned function is a
``tree_util.Partial`` pytree whose leaves are the residual arrays, so
``jax.device_put`` moves the whole stash to its device's ``pinned_host``
memory and back bit-identically (``to_host`` / ``to_device``). The stash
never leaves its device's backend, and it returns to the device it came
from.
"""
from __future__ import annotations

from typing import Any

import jax

from repro.core.schedule import FETCH, OFFLOAD
from repro.memory import policy as respol


def _to_memory(stash: Any, kind: str) -> Any:
    def move(a):
        if not isinstance(a, jax.Array):
            return a
        return jax.device_put(a, a.sharding.with_memory_kind(kind))
    return jax.tree.map(move, stash)


def to_host(stash: Any) -> Any:
    """Move a stash (any pytree — including a vjp closure) to the pinned
    host memory of the device each array lives on."""
    return _to_memory(stash, "pinned_host")


def to_device(stash: Any) -> Any:
    """Move an offloaded stash back into its own device's memory."""
    return _to_memory(stash, "device")


HOST_OFFLOAD = respol.register(respol.ResidencyPolicy(
    "host_offload", OFFLOAD, FETCH, mechanism="host",
    default_cap=respol.residency_cap,
    cap_roof=respol.residency_cap_roof))
