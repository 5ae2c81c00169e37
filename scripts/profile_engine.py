"""Phase profile of the MultiEngine serving round (VERDICT r4 item 2).

Replicates bench.py's engine scenario load shape (pending queues topped to
max_ents per group each round) and prints the per-phase share of the round
plus a micro-breakdown of the apply path.

Runs on the platform JAX finds and prints it with the result; a CPU
profile is asked for by name:

Usage: [JAX_PLATFORMS=cpu] python scripts/profile_engine.py [G] [rounds]
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from etcd_tpu.utils.platform import enable_compile_cache  # noqa: E402

enable_compile_cache()

import numpy as np  # noqa: E402

from etcd_tpu.server.engine import EngineConfig, MultiEngine  # noqa: E402
from etcd_tpu.server.request import Request  # noqa: E402


def main():
    G = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    n_rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 60
    E = 4
    P = 5
    payload = Request(method="PUT", path="/bench/k", val="x" * 64)

    with tempfile.TemporaryDirectory() as tmp:
        eng = MultiEngine(EngineConfig(
            groups=G, peers=P, data_dir=tmp, window=16, max_ents=E,
            heartbeat_tick=3, fsync=True, stagger=True,
            checkpoint_rounds=1 << 30))
        for _ in range(12):
            eng.run_round()
            if all(eng.leader_slot(g) >= 0 for g in range(G)):
                break
        assert all(eng.leader_slot(g) >= 0 for g in range(G))

        def offer():
            with eng._lock:
                for g in range(G):
                    dq = eng._pending[g]
                    while len(dq) < E:
                        rid = eng.reqid.next()
                        r = Request(**{**payload.__dict__, "id": rid})
                        dq.append((rid, b"\x00" + r.encode(), r))
                    eng._dirty.add(g)

        for _ in range(5):
            offer()
            eng.run_round()

        eng.phase_s = {}
        a0 = eng.acked_requests
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            offer()
            eng.run_round()
        elapsed = time.perf_counter() - t0
        acked = eng.acked_requests - a0

        total_ms = 1000.0 * elapsed / n_rounds
        import jax
        d = jax.devices()[0]
        print(f"\nplatform={d.platform} ({d.device_kind})")
        print(f"G={G} P={P} E={E} fsync=on: {n_rounds} rounds, "
              f"{total_ms:.2f} ms/round, {acked/elapsed:,.0f} acked "
              f"writes/s")
        ph = dict(eng.phase_s)
        acct = sum(ph.values())
        for k, v in sorted(ph.items(), key=lambda kv: -kv[1]):
            print(f"  {k:10s} {1000*v/n_rounds:9.3f} ms/round "
                  f"{100*v/elapsed:6.2f}% of wall")
        print(f"  {'(acct)':10s} {1000*acct/n_rounds:9.3f} ms/round "
              f"{100*acct/elapsed:6.2f}% of wall")
        eng.stop()


if __name__ == "__main__":
    main()
