#!/usr/bin/env python
"""True multi-PROCESS SPMD dry-run of the consensus kernel — the DCN
transport class of SURVEY §2.4 (reference rafthttp's role between hosts).

Each process is one "host" contributing 4 virtual CPU devices to a single
global ("groups", "peers") mesh, with the peers axis deliberately laid out
ACROSS processes: the kernel's per-round message routing (outbox→inbox
peer-axis swap) then lowers to an all_to_all whose edges cross process
boundaries — on real hardware, ICI within a slice and DCN between slices,
with XLA driving both (the TPU-native replacement for rafthttp streams).

Run standalone (spawns its own 2 processes):      python scripts/multihost_dryrun.py
Run as one rank (driven by the test or manually): MH_PROC_ID=0 MH_COORD=... python scripts/multihost_dryrun.py
"""
import os
import sys

N_PROCS = 2
LOCAL_DEVICES = 4


def run_rank(proc_id: int, coord: str) -> None:
    # A CPU dry run by design (two processes cannot share a chip): the
    # platform and device count must be set BEFORE distributed.initialize/
    # devices() instantiate a backend.
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={LOCAL_DEVICES}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    print(f"rank {proc_id}: initializing distributed ({coord})", flush=True)
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=N_PROCS, process_id=proc_id)
    print(f"rank {proc_id}: distributed up; local devices: "
          f"{jax.local_device_count()}", flush=True)
    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh

    from etcd_tpu.ops import kernel
    from etcd_tpu.ops.state import LEADER, KernelConfig, init_state
    from etcd_tpu.parallel.mesh import (mailbox_sharding, shard_state,
                                        state_sharding)

    devs = jax.devices()
    assert len(devs) == N_PROCS * LOCAL_DEVICES, devs
    # (groups=4, peers=2) with each peers-row holding one device from EACH
    # process: the routing all_to_all must cross the process boundary.
    arr = np.array(devs).reshape(N_PROCS, LOCAL_DEVICES).T
    mesh = Mesh(arr, axis_names=("groups", "peers"))
    procs_on_row = {d.process_index for d in arr[0]}
    assert len(procs_on_row) == N_PROCS, "peers axis does not cross processes"

    groups, peers = 16, 4
    cfg = KernelConfig(groups=groups, peers=peers, window=8, max_ents=2)
    st = shard_state(init_state(cfg, stagger=True), mesh)
    mb = mailbox_sharding(mesh)
    inbox = jax.device_put(
        jnp.zeros((groups, peers, peers, cfg.fields), jnp.int32), mb)
    zero = jnp.zeros(groups, jnp.int32)

    with mesh:
        for r in range(8):
            st, outbox = kernel.step(cfg, st, inbox, zero, zero,
                                     jnp.asarray(True))
            inbox = jax.device_put(kernel.route_local(outbox), mb)
            state = multihost_utils.process_allgather(st.state,
                                                      tiled=True)
            if (state == LEADER).sum(axis=1).min() >= 1:
                break
        state = multihost_utils.process_allgather(st.state, tiled=True)
        assert (state == LEADER).sum(axis=1).min() >= 1, \
            "multi-process election failed"

        slots = (state == LEADER).argmax(axis=1).astype(np.int32)
        commit0 = multihost_utils.process_allgather(st.commit, tiled=True)
        base = commit0[np.arange(groups), slots].copy()
        pc = jnp.ones(groups, jnp.int32)
        ps = jnp.asarray(slots)
        for r in range(6):
            st, outbox = kernel.step(cfg, st, inbox,
                                     pc if r == 0 else zero, ps,
                                     jnp.asarray(False))
            inbox = jax.device_put(kernel.route_local(outbox), mb)
        commit = multihost_utils.process_allgather(st.commit, tiled=True)
        commit = commit[np.arange(groups), slots]
        assert (commit >= base + 1).all(), "multi-process commit failed"

    print(f"rank {proc_id}: mesh {dict(zip(mesh.axis_names, arr.shape))} "
          f"across {N_PROCS} processes: elections + commits OK", flush=True)
    jax.distributed.shutdown()


def spawn_all() -> int:
    import socket
    import subprocess

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    procs = []
    for pid in range(N_PROCS):
        env = dict(os.environ, MH_PROC_ID=str(pid), MH_COORD=coord)
        env.pop("XLA_FLAGS", None)   # ranks set their own device count
        procs.append(subprocess.Popen([sys.executable,
                                       os.path.abspath(__file__)], env=env))
    # ONE shared deadline, shorter than any caller's kill timeout
    # (tests/test_multihost.py uses 560s): on a hung gloo collective the
    # spawner must kill BOTH ranks itself — dying first would orphan them
    # on the coordinator port. (Per-process timeouts would stack.)
    import time
    deadline = time.time() + 420
    try:
        rcs = [p.wait(timeout=max(1.0, deadline - time.time()))
               for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            p.wait()
        print("FAILED: ranks hung; killed", file=sys.stderr)
        return 1
    if any(rcs):
        print(f"FAILED: ranks exited {rcs}", file=sys.stderr)
        return 1
    print(f"all {N_PROCS} ranks OK")
    return 0


if __name__ == "__main__":
    if "MH_PROC_ID" in os.environ:
        run_rank(int(os.environ["MH_PROC_ID"]), os.environ["MH_COORD"])
    else:
        sys.exit(spawn_all())
