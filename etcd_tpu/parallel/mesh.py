"""Mesh + sharding layout for the consensus kernel.

The domain's parallelism axes (SURVEY.md §2.2) map onto a 2-D device mesh:

- "groups": the multi-tenant batch axis — independent Raft groups, the moral
  equivalent of data parallelism. Arbitrarily shardable: groups never
  communicate with each other, so XLA inserts NO collectives along it.
- "peers": the replication axis — peer slots of each group, the moral
  equivalent of model parallelism. When sharded, the per-round message
  routing (outbox[g, from, to] -> inbox[g, to, from], a transpose of the two
  peer axes) becomes an all_to_all that XLA lays onto ICI; this is the
  TPU-native replacement for the reference's rafthttp streams
  (rafthttp/stream.go, pipeline.go).

In a real multi-host deployment each host is a failure domain holding one
peer slot of every group (peers axis sharded across hosts over DCN); on a
single pod/chip both axes are just throughput axes.

The multi-host shape is executable TODAY without TPU pods:
scripts/multihost_dryrun.py boots N OS processes into one global mesh via
jax.distributed (gloo CPU collectives) with the peers axis crossing
process boundaries, and runs elections + commits through cross-process
routing collectives (tests/test_multihost.py keeps it green).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from etcd_tpu.ops.state import GroupState


def make_mesh(devices=None, peers_axis: int = 1) -> Mesh:
    """A ("groups", "peers") mesh. peers_axis devices are dedicated to the
    replication axis (1 = all devices on the groups axis)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % peers_axis != 0:
        raise ValueError(f"{n} devices not divisible by peers_axis={peers_axis}")
    arr = np.array(devices).reshape(n // peers_axis, peers_axis)
    return Mesh(arr, axis_names=("groups", "peers"))


def state_sharding(mesh: Mesh) -> GroupState:
    """NamedSharding pytree matching GroupState: every array is sharded on
    its leading group axis and (where present) the first peer axis; the
    target-peer axis and the log window stay replicated within a shard."""
    gp = NamedSharding(mesh, P("groups", "peers"))
    gpx = NamedSharding(mesh, P("groups", "peers", None))
    return GroupState(
        term=gp, vote=gp, commit=gp, lead=gp, state=gp, elapsed=gp, prng=gp,
        log_term=gpx, last_index=gp,
        match=gpx, next=gpx, pr_state=gpx, paused=gpx, ack_age=gpx,
        votes=gpx,
        peer_mask=gp, need_host=gp,
    )


def mailbox_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for inbox/outbox (G, P, P, F): shard groups + first peer
    axis. Routing (swapaxes 1<->2) then compiles to an all_to_all over the
    "peers" mesh axis."""
    return NamedSharding(mesh, P("groups", "peers", None, None))


def flag_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for the compact step's (G, P) change-flag map: as the
    (G, P) state fields it is computed from, so the diff needs no
    collective."""
    return NamedSharding(mesh, P("groups", "peers"))


def group_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a per-group (G,) array: the read step's confirmed flag
    and captured commit index."""
    return NamedSharding(mesh, P("groups"))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Every device holds the whole value: the compact step's need-host
    attestation and the rows gather_rows collects from the shards."""
    return NamedSharding(mesh, P())


def shard_state(st: GroupState, mesh: Mesh) -> GroupState:
    """Place a host-built GroupState onto the mesh."""
    sh = state_sharding(mesh)
    return jax.tree.map(jax.device_put, st, sh)
