"""Slot faults for the served engine, on one seeded schedule of rounds:
which follower slots are HELD in which round (LagSchedule), and which
leaders are DOWN, cut off from their peers (ChurnSchedule, at the end).

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


def _phase_window(sch, lo: int, width: int) -> np.ndarray:
    """The groups of schedule `sch` whose phase lies in lo .. lo + width - 1
    (mod its period): a window of the groups sorted by phase, one that may
    wrap, found by binary searches, so a round's work is over the groups in
    it and not over all G."""
    a, b = np.searchsorted(sch._sorted, (lo, lo + width))
    idx = sch._order[a:b]
    if lo + width > sch.period:         # the window wraps
        c = np.searchsorted(sch._sorted, lo + width - sch.period)
        idx = np.concatenate((sch._order[:c], idx))
    return idx


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
        # The groups by phase (_phase_window).
        self._order = np.argsort(self.phase, kind="stable")
        self._sorted = self.phase[self._order]

    def holding(self, round_no: int) -> np.ndarray:
        """The groups that hold a follower in `round_no`: g holds while
        (round_no + phase[g]) % period < hold."""
        return _phase_window(self, -round_no % self.period, self.hold)

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


class ChurnSchedule:
    """Leader-election churn (BASELINE.json configs[4]; the reference's
    functional tester calls the failure killLeaderForLong / isolate,
    etcd-tester/failure.go): every group's leader is DOWN for `down_rounds`
    of every `period_rounds` rounds. A down slot loses every message to it
    and from it at every hop (kernel `down`), so the other slots elect a
    successor while it goes on believing it leads (this Raft, like the
    reference's, has no check-quorum), and when the cut ends it finds a
    higher term, steps down and has its log truncated to its successor's,
    or is snapshot-installed if that has left the ring.

    Which groups are in a cut in which round is a pure function of
    (seed, g, round_no): the phases are spread evenly over the period by a
    seeded permutation, so ~G / period cuts start a round and a share
    down / period of the groups runs one peer short at every round. WHO is
    cut is the group's working leader as the host sees it in the cut's
    first round (the active LEADER row of the highest term), at most one
    slot a group, none in a group without a leader or with fewer than three
    active slots: P - 1 of P >= 3 peers stay in the protocol and a quorum
    is never at risk. The engine keeps the (G, P) map between rounds; after
    a restart `recover` tells it from the journalled terms and votes
    (roles are not journalled)."""

    def __init__(self, groups: int, peers: int, down_rounds: int,
                 period_rounds: int, seed: int) -> None:
        if peers < 3:
            raise ValueError("leader churn needs at least 3 peers a group: "
                             "cutting off a slot of fewer risks the quorum")
        if not 1 <= down_rounds < period_rounds:
            raise ValueError("churn_down_rounds must be in "
                             "[1, churn_period_rounds)")
        self.down = int(down_rounds)
        self.period = int(period_rounds)
        rng = np.random.default_rng(seed)
        self.phase = (rng.permutation(groups).astype(np.int64)
                      * self.period) // groups
        self._order = np.argsort(self.phase, kind="stable")
        self._sorted = self.phase[self._order]

    def _at(self, offset: int, round_no: int, width: int = 1) -> np.ndarray:
        """The groups g with offset <= (round_no + phase[g]) % period <
        offset + width."""
        return _phase_window(self, (offset - round_no) % self.period, width)

    def starting(self, round_no: int) -> np.ndarray:
        """The groups whose cut begins in `round_no`."""
        return self._at(0, round_no)

    def ending(self, round_no: int) -> np.ndarray:
        """The groups whose cut has ended: `round_no` is the first round
        their slot is up again."""
        return self._at(self.down, round_no)

    def cutting(self, round_no: int) -> np.ndarray:
        """The groups in a cut in `round_no`."""
        return self._at(0, round_no, self.down)

    def recover(self, round_no: int, mask: np.ndarray, term: np.ndarray,
                vote: np.ndarray) -> np.ndarray:
        """(G, P) bool: the slots that were down in `round_no`, told after
        a restart from what the WAL holds. A slot cut off keeps the term
        and the self-vote it led with while the others move on to its
        successor's term: it is the one active slot of the lowest term,
        where the terms differ; where they do not (no successor yet) it is
        the slot a quorum of the group voted for in that term. A group for
        which neither names exactly one slot goes on with all its peers."""
        down = np.zeros(mask.shape, bool)
        for g in self.cutting(round_no).tolist():
            act = np.nonzero(mask[g])[0]
            if len(act) < 3:
                continue
            t, v = term[g, act], vote[g, act]
            own = v == act + 1
            if t.min() < t.max():
                cand = act[own & (t == t.min())]
            else:
                cand = act[own & (np.bincount(v, minlength=len(mask[g]) + 1)
                                  [act + 1] > len(act) // 2)]
            if len(cand) == 1:
                down[g, cand[0]] = True
        return down
