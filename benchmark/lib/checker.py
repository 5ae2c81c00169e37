"""The plain reference and the comparison that decides `correct`.

The reference is a Python dict fed the same operations as the member: per
(tenant, key) the last value whose write was acknowledged, and every write
of the same key that got no reply (it may or may not have been applied, and
on a severed connection it may be applied late). Each key has one writer
(the client id is in the key) and a client has one request in flight, so
"last acknowledged" is the client's own order.

Guarantees held (the configuration files state them):
  - an acknowledged write is read back with ?quorum=true (a majority of the
    group's replicas), before and after SIGKILL + restart;
  - a ?quorum=true read returns the last acknowledged value (linearizable:
    preloaded keys never change, so the answer is exact);
  - tenants are isolated: a key written in one tenant is not visible in
    another.
Every number compared is exact: its limit is 0.
"""
from __future__ import annotations

import hashlib
import random

ABSENT = None      # what a read of a key that was never written returns


def value_for(seed: int, who, seq: int, nbytes: int) -> str:
    """The value of write number `seq` of client (or preload tenant) `who`:
    `nbytes` ASCII characters derived from (seed, who, seq), so the
    reference needs no copy of what was sent."""
    h = hashlib.blake2b(f"{seed}/{who}/{seq}".encode(),
                        digest_size=32).hexdigest()
    return (h * (nbytes // len(h) + 1))[:nbytes]


class Reference:
    """{(tenant, key): [acked value or ABSENT, [unacknowledged values]]}."""

    def __init__(self) -> None:
        self.model: dict = {}

    def sent(self, tenant: int, key: str, value: str) -> None:
        """A write left the client; until it is acknowledged it may or may
        not take effect."""
        self.model.setdefault((tenant, key), [ABSENT, []])[1].append(value)

    def acked(self, tenant: int, key: str, value: str) -> None:
        slot = self.model[(tenant, key)]
        slot[0] = value
        slot[1].remove(value)

    def allowed(self, tenant: int, key: str) -> list:
        """Values a linearizable read may return now."""
        slot = self.model.get((tenant, key))
        if slot is None:
            return [ABSENT]
        return [slot[0], *slot[1]]

    def acked_keys(self) -> list:
        return sorted(k for k, s in self.model.items() if s[0] is not ABSENT)

    def merge(self, other_model: dict) -> None:
        dup = self.model.keys() & other_model.keys()
        if dup:
            raise ValueError(f"two writers for {sorted(dup)[:3]}")
        self.model.update(other_model)

    # JSON cannot key by tuple: rows of [tenant, key, acked, later].
    def dump(self) -> list:
        return [[t, k, s[0], s[1]] for (t, k), s in self.model.items()]

    @staticmethod
    def load(rows: list) -> dict:
        return {(t, k): [a, later] for t, k, a, later in rows}


def sample_keys(ref: Reference, seed: int, n: int) -> list:
    """A seeded sample of up to n acknowledged (tenant, key) pairs."""
    keys = ref.acked_keys()
    rng = random.Random(seed ^ 0x5EED)
    return keys if len(keys) <= n else rng.sample(keys, n)


def isolation_probe(ref: Reference, keys: list, groups: int):
    """(other tenant, key) for the first sampled key whose neighbour tenant
    never saw a write of that key: reading it there must find nothing."""
    for t, key in keys:
        other = (t + 1) % groups
        if other != t and (other, key) not in ref.model:
            return other, key
    return None


def compare_reads(ref: Reference, answers: dict) -> list:
    """answers: {(tenant, key): value or ABSENT}. The reads that returned
    something the reference does not allow, as (tenant, key, got)."""
    return [(t, k, got) for (t, k), got in answers.items()
            if got not in ref.allowed(t, k)]


def verdict(numbers: dict) -> tuple:
    """numbers: {name: value}, every limit 0. (correct, lines to print)."""
    lines = [{"check": name, "value": value, "limit": 0}
             for name, value in numbers.items()]
    return all(v == 0 for v in numbers.values()), lines
