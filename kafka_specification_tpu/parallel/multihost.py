"""Multi-host (DCN) support for the sharded engine (SURVEY.md §2.6).

TLC's distributed mode spreads workers over TLCServer/TLCWorker JVMs; the
TPU-native equivalent runs the SAME `check_sharded` host loop on every
process of a multi-host program (`jax.distributed.initialize`), with the
1-D frontier mesh spanning all hosts' devices.  XLA then lays the
`all_to_all` fingerprint exchange over ICI within a slice and DCN across
slices — no hand-written networking, exactly like the NCCL-less design the
north star prescribes.

Controller model: REPLICATED HOST LOOP.  Every process executes the same
deterministic Python loop over the same global (host-side) frontier data,
so control decisions (chunk splits, bucket sizes, retries, termination)
agree everywhere without a coordinator:

- `put_global`  — device placement: each process contributes only its
  addressable shards (`jax.make_array_from_process_local_data`); on a
  single process it degrades to `jax.device_put`.
- `fetch_global` — result readback: all-gathers non-addressable shards
  (`multihost_utils.process_allgather`) so every process sees the same
  global ndarray; single-process it is `np.asarray`.

Both helpers are in the check_sharded hot path already, so the engine is
multi-host-shaped by construction; this module is the only place that
distinguishes the two regimes.  The host-FpSet spill backend is per-host
owned: each process keeps FpSets only for the shards whose devices it
hosts, computes their novelty masks locally, and the masks are OR-merged
across processes (`or_across_processes`) so the replicated loop stays in
lockstep — host memory and insert work both scale down 1/P.

This environment has a single host, so the
multi-process regime is exercised only via the single-process degenerate
path plus `dryrun_multichip`'s virtual mesh; the code paths are kept
explicit and small so a real pod can validate them directly.
"""

from __future__ import annotations

import os

import jax
import numpy as np


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> dict:
    """Initialize JAX's multi-host runtime if configured; no-op otherwise.

    Explicit args win; else the standard env vars drive it
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID); any
    field left unset is passed as None so jax.distributed's own cluster
    auto-detection (SLURM / TPU pod metadata) fills it in.  Also runs
    initialize() with all-None args when KSPEC_MULTIHOST=1, for clusters
    that are fully auto-detectable.  Returns {"process_id",
    "process_count", "local_devices", "global_devices"}.
    """
    addr = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    want = addr is not None or os.environ.get("KSPEC_MULTIHOST") == "1"
    if want:
        if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["JAX_NUM_PROCESSES"])
        if process_id is None and "JAX_PROCESS_ID" in os.environ:
            process_id = int(os.environ["JAX_PROCESS_ID"])
        # NB: must run before anything initializes the XLA backend (even
        # jax.process_count() would), so no jax queries happen first
        try:
            try:
                jax.distributed.initialize(
                    coordinator_address=addr,
                    num_processes=num_processes,
                    process_id=process_id,
                )
            except ValueError:
                if addr is None or num_processes is not None or process_id is not None:
                    raise
                # explicit coordinator, no topology given anywhere, and
                # jax's cluster auto-detection found nothing -> the
                # 1-process degenerate launch (the testable path here)
                jax.distributed.initialize(
                    coordinator_address=addr, num_processes=1, process_id=0
                )
        except RuntimeError as e:
            # idempotent re-entry (e.g. resume path): already initialized
            if "already" not in str(e).lower():
                raise
    return {
        "process_id": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def put_global(arr: np.ndarray, sharding):
    """Place a (host-replicated) global ndarray onto the mesh.

    Single process: plain device_put.  Multi-process: every process holds
    the same global array (replicated host loop), so each contributes its
    addressable shards via make_array_from_process_local_data.
    """
    if not is_multiprocess():
        return jax.device_put(arr, sharding)
    # local data = the rows this process's devices own; for a 1-D sharding
    # over contiguous equal shards this is a contiguous slice
    return jax.make_array_from_process_local_data(
        sharding, _local_slice(arr, sharding), arr.shape
    )


def _local_slice(arr: np.ndarray, sharding) -> np.ndarray:
    idx = sharding.addressable_devices_indices_map(arr.shape)
    slices = list(idx.values())
    # contiguity holds for the engine's 1-D meshes (devices in mesh order)
    starts = sorted(s[0].start or 0 for s in slices)
    stops = sorted(s[0].stop if s[0].stop is not None else arr.shape[0] for s in slices)
    return arr[starts[0] : stops[-1]]


def fetch_global(garr) -> np.ndarray:
    """Read a possibly multi-host-sharded jax.Array back as the full global
    ndarray, identical on every process."""
    if not is_multiprocess():
        return np.asarray(garr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(garr, tiled=True))


def is_coordinator() -> bool:
    """True on the process that performs singleton side effects
    (checkpoint writes, stats files)."""
    return jax.process_index() == 0


def or_across_processes(arr: np.ndarray) -> np.ndarray:
    """Element-wise OR of a boolean ndarray across all processes.

    The host-FpSet novelty masks are computed only by each shard's owner
    process (per-host set ownership); OR-merging them gives every process
    the identical global mask the replicated host loop requires.
    Single-process: identity.
    """
    if not is_multiprocess():
        return arr
    from jax.experimental import multihost_utils

    g = multihost_utils.process_allgather(arr.astype(np.uint8))  # [P, ...]
    return np.asarray(g).any(axis=0)
