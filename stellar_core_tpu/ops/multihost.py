"""Multi-host device meshes for the batch-verify service.

Reference analogue: the node's distributed comm backend (SURVEY.md §5.8).
Consensus traffic stays byte-exact XDR over the TCP overlay; THIS module
only scales the crypto service itself across accelerators:

- within a host, signatures shard over the chips on the ICI mesh axis;
- across hosts, over the DCN axis (slow network — each host keeps its
  own signature shard local, so DCN carries only the boolean
  result gather, never the tuples);
- the workload is embarrassingly data-parallel (SURVEY.md §5.7): no
  ring/all-to-all exchange exists because signatures share no state.

`initialize_distributed` wraps jax.distributed for multi-process
(one process per host) deployments; `make_hybrid_mesh` builds the
(dcn, ici) mesh; `ShardedBatchVerifier` accepts any 1-D mesh, and
`HybridShardedVerifier` flattens the 2-D hybrid mesh into the batch
axis with shard_map.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec as PSpec
from jax import shard_map

from . import ed25519_kernel
from .verifier import (DEVICE_MIN_BATCH, MIN_BUCKET, ShardedBatchVerifier,
                       TpuBatchVerifier)


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """One-per-host jax.distributed init (no-op when single-process).
    In a multi-host pod each node service calls this before building the
    hybrid mesh; the coordinator address travels in the node config, the
    same way the reference distributes peer addresses via cfg
    (KNOWN_PEERS) rather than a discovery service."""
    if num_processes is None or num_processes <= 1:
        return
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def make_hybrid_mesh(devices: Optional[Sequence] = None,
                     n_hosts: Optional[int] = None) -> Mesh:
    """(dcn, ici) mesh: axis 0 spans hosts (slow network), axis 1 the
    chips within a host (fast ICI). With explicit `devices`/`n_hosts`
    (tests: a virtual CPU mesh standing in for N hosts x M chips), the
    flat device list is folded; in production the shape comes from
    jax.process_count()."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if n_hosts is None:
        n_hosts = max(1, jax.process_count())
    per_host = len(devices) // n_hosts
    assert per_host * n_hosts == len(devices), \
        f"{len(devices)} devices do not fold into {n_hosts} hosts"
    grid = np.array(devices).reshape(n_hosts, per_host)
    return Mesh(grid, ("dcn", "ici"))


def make_hybrid_verify(mesh: Mesh,
                       kernel=ed25519_kernel.verify_kernel_full):
    """shard_map'd verify over BOTH mesh axes: the (B,32) uint8 batch
    axis shards over dcn x ici jointly (pure dp). The only cross-device
    traffic is the (B,) bool gather — DCN never carries signatures."""
    spec = PSpec(("dcn", "ici"), None)
    f = shard_map(kernel, mesh=mesh,
                  in_specs=(spec,) * 4, out_specs=PSpec(("dcn", "ici")))
    return jax.jit(f)


class HybridShardedVerifier(ShardedBatchVerifier):
    """Data-parallel batch verifier over a 2-D (dcn, ici) hybrid mesh.

    The full-mesh program shards over both axes jointly (DCN carries
    only the result gather); the per-device health machinery is
    inherited from ShardedBatchVerifier over the FLATTENED device
    list, so a sick chip shrinks the hybrid mesh the same way — a
    degraded active set collapses to a 1-D mesh over the survivors
    (host boundaries stop mattering once the grid is ragged; the
    workload has no cross-shard traffic to place anyway)."""

    def __init__(self, mesh: Optional[Mesh] = None, perf=None,
                 device_min_batch=DEVICE_MIN_BATCH, metrics=None):
        full = mesh if mesh is not None else make_hybrid_mesh()
        super().__init__(devices=list(full.devices.flat), axis="dp",
                         perf=perf, device_min_batch=device_min_batch,
                         metrics=metrics)
        self.mesh = full

    def _compile(self, active, msg32):
        if len(active) == self.ndev:
            kernel = (ed25519_kernel.verify_kernel_msg32 if msg32
                      else ed25519_kernel.verify_kernel_full)
            return (make_hybrid_verify(self.mesh, kernel), None)
        return super()._compile(active, msg32)
