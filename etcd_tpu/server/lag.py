"""Lagging-follower injection for the served engine: which follower slots
are HELD in which round.

Fault injection for measurement (BASELINE.json configs[3]: "5% lagging-
follower injection (Progress.Paused)"; the reference's functional tester
calls the same failure killOneForLong / isolate, etcd-tester/failure.go):
a held follower gets no append and no snapshot from its leader, however
far behind it falls (kernel._assemble_sends gates both), while heartbeats,
votes and responses flow, so it keeps its leader and its term and no
election is caused. On release the ordinary protocol takes over: appends
while the follower is within the leader's ring, the host's
snapshot-install beyond it.

The schedule is a pure function of (seed, g, round_no), so a restart from
the WAL continues it (round numbers are journalled) and two runs hold the
same followers in the same rounds:

- group g holds ONE follower for `hold_rounds` out of every `period` =
  hold_rounds / ((peers - 1) * share) rounds, so that at every round a
  share `share` of all follower slots is held, at most one a group;
- the groups' phases are spread evenly over the period by a seeded
  permutation: holds start and end a few groups a round, never all at
  once;
- which follower (by rank among the group's non-leader slots) is drawn
  from the seed and g and moves on by one each period.

What is held never puts a quorum at risk: only a group that has a leader
and at least two other active slots holds one, so P - 1 of P >= 3 peers
stay in the protocol.
"""
from __future__ import annotations

import numpy as np

_LEADER = 2  # ops.state.LEADER


class LagSchedule:

    def __init__(self, groups: int, peers: int, share: float,
                 hold_rounds: int, seed: int) -> None:
        if peers < 3:
            raise ValueError("lag injection needs at least 3 peers a group: "
                             "holding a follower of fewer risks the quorum")
        if hold_rounds < 1:
            raise ValueError("lag_hold_rounds must be >= 1")
        if not 0.0 < share * (peers - 1) <= 1.0:
            raise ValueError(
                f"lag_share must be in (0, 1/{peers - 1}]: at most one "
                "follower a group is held")
        self.hold = int(hold_rounds)
        self.period = max(self.hold,
                          round(self.hold / (share * (peers - 1))))
        rng = np.random.default_rng(seed)
        self.phase = (rng.permutation(groups).astype(np.int64)
                      * self.period) // groups
        self.rank0 = rng.integers(0, peers - 1, size=groups)
        # The groups by phase: those that hold in a round are a window of
        # this order (one that wraps), found by two binary searches, so a
        # round's work is over the holding groups and not over all G.
        self._order = np.argsort(self.phase, kind="stable")
        self._sorted = self.phase[self._order]

    def holding(self, round_no: int) -> np.ndarray:
        """The groups that hold a follower in `round_no`: g holds while
        (round_no + phase[g]) % period < hold."""
        lo = -round_no % self.period        # phases lo .. lo + hold - 1
        a, b = np.searchsorted(self._sorted, (lo, lo + self.hold))
        idx = self._order[a:b]
        if lo + self.hold > self.period:    # the window wraps
            c = np.searchsorted(self._sorted, lo + self.hold - self.period)
            idx = np.concatenate((self._order[:c], idx))
        return idx

    def held(self, round_no: int, mask: np.ndarray,
             state: np.ndarray) -> np.ndarray:
        """(G, P) bool: the slots held in `round_no`, given the active
        mask and the roles as the host last saw them. Never a leader's
        slot, never more than one a group, none in a group without a
        leader or with fewer than two followers."""
        idx = self.holding(round_no)
        m = mask[idx]
        lead = m & (state[idx] == _LEADER)
        cand = m & ~lead
        # (column by column: P is small, and numpy's reductions along a
        # short last axis cost more than the whole rest of this)
        n_f = np.zeros(len(idx), np.int64)
        has_lead = np.zeros(len(idx), bool)
        for p in range(mask.shape[1]):
            n_f += cand[:, p]
            has_lead |= lead[:, p]
        # which follower: by rank, moving on by one each period
        rank = self.rank0[idx] + (round_no + self.phase[idx]) // self.period
        pick = rank % np.maximum(n_f, 1)
        ok = has_lead & (n_f >= 2)
        held = np.zeros(mask.shape, bool)
        seen = np.zeros(len(idx), np.int64)
        for p in range(mask.shape[1]):
            c = cand[:, p]
            held[idx, p] = c & ok & (seen == pick)
            seen += c
        return held
