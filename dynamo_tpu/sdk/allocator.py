"""TPU slice allocator for the local `serve` orchestrator.

Assigns each service worker a disjoint, CONTIGUOUS set of TPU chips (the
reference's GPU allocator assigns CUDA_VISIBLE_DEVICES ranges,
deploy/dynamo/sdk/cli/allocator.py:35-101). Contiguity matters on TPU:
neighboring chips share ICI links, so a slice split across the board pays
DCN-class latency for what should be ICI collectives. On TPU VMs chip
visibility is controlled with ``TPU_VISIBLE_DEVICES``; a process that takes
only part of the host's chips must also be told the shape of its sub-slice
and that it is the only process in it (:func:`tpu_process_env`), or its TPU
runtime tries to bring up the whole host and collides with its neighbours.
For hermetic CPU runs the same request becomes a virtual device count
(``--xla_force_host_platform_device_count``).

Beyond the round-4 bump allocator: per-allocation release (a restarted
worker's chips return to the pool instead of leaking until ``release_all``),
best-fit placement over free runs (limits fragmentation under churn), and
per-service placement tracking (``placements()`` — the disjointness
invariant is inspectable, not implicit).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


class AllocationError(RuntimeError):
    pass


# chips granted -> the sub-slice shape the TPU runtime is told (x,y,z)
_SLICE_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
_TPU_PROCESS_PORT0 = 8476


def tpu_process_env(chips: List[int]) -> Dict[str, str]:
    """Environment that confines one process to ``chips`` of a TPU host
    shared with other processes: visibility, the sub-slice's shape, a
    single-process "slice" of its own, and a runtime port no neighbour uses
    (derived from the first chip, so disjoint grants get disjoint ports).
    A count that is no rectangular sub-slice (3, 5, ...) gets visibility
    only and is left to the runtime."""
    visible = {"TPU_VISIBLE_DEVICES": ",".join(map(str, chips))}
    bounds = _SLICE_BOUNDS.get(len(chips))
    if bounds is None:
        return visible
    port = _TPU_PROCESS_PORT0 + chips[0]
    return {
        **visible,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # the same two under their older names, which a host image may
        # already export for the whole board (v5e hosts do: 2,2,1 / 1,1,1)
        "TPU_CHIPS_PER_HOST_BOUNDS": bounds,
        "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    }


@dataclass
class Allocation:
    """One worker's chip grant. ``env`` is what the worker process gets."""

    service: str
    chips: List[int]
    env: Dict[str, str] = field(default_factory=dict)


class TpuAllocator:
    """Hands out contiguous chip ranges; ``platform='cpu'`` hands out
    virtual device counts instead (no exclusivity needed)."""

    def __init__(self, total_chips: int = 4, platform: str = "tpu"):
        self.total = total_chips
        self.platform = platform
        self._free = set(range(total_chips))
        self._allocs: List[Allocation] = []

    # ------------------------------------------------------------------
    def _free_runs(self) -> List[List[int]]:
        """Maximal runs of contiguous free chips, ascending."""
        runs: List[List[int]] = []
        cur: List[int] = []
        for c in sorted(self._free):
            if cur and c == cur[-1] + 1:
                cur.append(c)
            else:
                if cur:
                    runs.append(cur)
                cur = [c]
        if cur:
            runs.append(cur)
        return runs

    def allocate(self, n_chips: int, service: str = "") -> Dict[str, str]:
        """Env for a worker needing ``n_chips`` accelerator chips (0 => a
        pure-CPU service; it must not initialize the TPU)."""
        return self.allocate_handle(n_chips, service=service).env

    def allocate_handle(self, n_chips: int, service: str = "") -> Allocation:
        """Like :meth:`allocate` but returns the :class:`Allocation` so the
        caller can :meth:`release` it individually (worker restart)."""
        if n_chips <= 0:
            return Allocation(service, [], {"JAX_PLATFORMS": "cpu"})
        if self.platform == "cpu":
            return Allocation(service, [], {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": ("--xla_force_host_platform_device_count="
                              f"{n_chips}"),
            })
        # best-fit: the smallest contiguous run that fits, so large future
        # requests keep a chance at the big runs
        candidates = [r for r in self._free_runs() if len(r) >= n_chips]
        if not candidates:
            raise AllocationError(
                f"need {n_chips} contiguous chips for {service or 'worker'}; "
                f"free runs: {[len(r) for r in self._free_runs()]} "
                f"of {self.total} total")
        run = min(candidates, key=len)
        chips = run[:n_chips]
        self._free.difference_update(chips)
        alloc = Allocation(service, chips, tpu_process_env(chips))
        self._allocs.append(alloc)
        return alloc

    def release(self, alloc: Allocation) -> None:
        """Return one worker's chips to the pool (restart path). Identity
        match, not equality: a re-grant of the same chips produces an
        EQUAL dataclass, and releasing a stale handle twice must not free
        the new owner's live grant."""
        for i, a in enumerate(self._allocs):
            if a is alloc:
                del self._allocs[i]
                self._free.update(alloc.chips)
                return

    def release_all(self) -> None:
        self._free = set(range(self.total))
        self._allocs.clear()

    def placements(self) -> Dict[str, List[List[int]]]:
        """service -> list of chip sets currently granted (disjointness and
        contiguity are directly checkable by callers/tests)."""
        out: Dict[str, List[List[int]]] = {}
        for a in self._allocs:
            out.setdefault(a.service or "worker", []).append(list(a.chips))
        return out
