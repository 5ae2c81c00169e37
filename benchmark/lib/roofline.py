"""What one device step must move, and the least time that takes.

The serving step (ops/kernel.py step_routed_compact / step_routed_read_auto)
takes the whole SoA consensus state and the routed inbox and returns both
(donated, so in place). It does integer compares and selects, no matrix
work, so the bound that can bind is memory: every array read once and
written once. The three message hops chained inside one step re-read state
that could in principle stay on chip, so this is the floor, not the
program's own traffic.

Bytes per array follow ops/state.py GroupState and the engine's inbox
(G, P, P, 8 + max_ents) int32; benchmark/tests/test_roofline.py holds this
count against the summed nbytes of the kernel's real arrays.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
N_FIXED_FIELDS = 8
MAX_ENTS = 8        # EngineConfig.max_ents; no flag of the served entry
                    # point reaches it (tests/test_roofline.py holds it to
                    # the program's default)


def state_bytes(G: int, P: int, W: int) -> int:
    gp, gpp = G * P, G * P * P
    return (9 * 4 * gp        # term vote commit lead state elapsed prng
                              # last_index need_host: (G,P) 32-bit
            + 1 * gp          # peer_mask (G,P) bool
            + 4 * gp * W      # log_term (G,P,W) int32
            + 5 * 4 * gpp     # match next pr_state ack_age votes (G,P,P) i32
            + 1 * gpp)        # paused (G,P,P) bool


def inbox_bytes(G: int, P: int, max_ents: int) -> int:
    return 4 * G * P * P * (N_FIXED_FIELDS + max_ents)


def step_min_bytes(G: int, P: int, W: int, max_ents: int) -> int:
    """State and inbox, each read once and written once."""
    return 2 * (state_bytes(G, P, W) + inbox_bytes(G, P, max_ents))


def peaks_for(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS}: add "
                       "its published peaks with their source")
    return table[device_kind]


def step_min_seconds(rows: int, peers: int, window: int,
                     device_kind: str) -> float:
    """rows: the groups ONE device holds (/engine/status device_rows: a
    mesh runs the step on every chip at once). Memory-bound: bytes over the
    HBM peak."""
    b = step_min_bytes(rows, peers, window, MAX_ENTS)
    return b / peaks_for(device_kind)["hbm_bytes_per_s"]
