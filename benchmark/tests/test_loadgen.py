"""The generator's two loops and its tenant choice, on the CPU with no
member: the closed loop draws what it drew before the open loop came (pinned
on the parent's code); an open-loop schedule comes from the seed and has the
rate it was asked; against a server that stands still for half a second the
open loop's tail, taken from the due times, shows the stall and the closed
loop's does not (why an open-loop cell is wanted); lateness, pool-dry count
and backlog are reported; Zipf ranks have the frequencies 1 / r^theta and
come from the seed."""
import hashlib
import json
import os
import socketserver
import threading
import time

import checker
import loadgen
import pytest
import run
from test_run_end_to_end import read_request

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix_file(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# sha256 over "kind,tenant,key,value;" of the first 1,000 requests of
# MixClient(seed=1, cid=0..3, groups=12,500), taken on the parent of PR 31
PINNED = {
    "put256-c256":
        "c08b0f3121844707d16f1f323976e53a9c649288a76109a476c7d6027b04dc34",
    "qget-c256":
        "2d5bab571e04265c9d9418d53a26f18fdc832e84f6afa2c67a3ad2e405a10214",
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_closed_loop_draws_are_pinned(name):
    mix, h, ref = mix_file(name), hashlib.sha256(), checker.Reference()
    for cid in range(4):
        c = loadgen.MixClient(1, cid, mix, 12_500, ref,
                              loadgen.tenant_dist(1, mix, 12_500))
        for _ in range(1000):
            _, (kind, tenant, key, value) = c.next()
            h.update(f"{kind},{tenant},{key},{value};".encode())
    assert h.hexdigest() == PINNED[name]


def test_open_schedule_comes_from_the_seed_and_has_its_rate():
    rate, seconds = 975.0, 12.0
    a = loadgen.open_schedule(2**31 + 7, 0, 0, rate, seconds)
    assert a == loadgen.open_schedule(2**31 + 7, 0, 0, rate, seconds)
    for other in ((2**31 + 8, 0, 0), (2**31 + 7, 1, 0), (2**31 + 7, 0, 1)):
        assert a[:5] != loadgen.open_schedule(*other, rate, seconds)[:5]
    assert a == sorted(a) and 0 < a[0] and a[-1] < seconds
    gaps = [y - x for x, y in zip(a, a[1:])][:10_000]
    assert len(gaps) == 10_000
    assert abs(sum(gaps) / len(gaps) * rate - 1) < 0.03
    # exponential gaps: the standard deviation is the mean
    mean = sum(gaps) / len(gaps)
    sd = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5
    assert abs(sd / mean - 1) < 0.05


class StallingServer:
    """Answers every PUT at once with what a member would say, but for one
    stall: from `stall_at` (monotonic) it stands still for `stall_s`."""

    def __init__(self) -> None:
        outer = self
        self.stall_at, self.stall_s = float("inf"), 0.0

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                while True:
                    req = read_request(self.rfile)
                    if req is None:
                        return
                    head, body = req
                    left = outer.stall_at + outer.stall_s - time.monotonic()
                    if 0 < left <= outer.stall_s:
                        time.sleep(left)
                    key = head.split(b" ")[1].split(b"/v2/keys")[1]
                    doc = json.dumps({"action": "set", "node": {
                        "key": key.decode(),
                        "value": body.decode()[len("value="):]}}).encode()
                    self.wfile.write(b"HTTP/1.1 200 OK\r\nContent-Length: "
                                     b"%d\r\n\r\n%s" % (len(doc), doc))

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True
            request_queue_size = 128

        self.server = Server(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()


MIX = {"loop": "closed", "clients": 8, "gen_procs": 1, "write_share": 1.0,
       "value_bytes": 16, "keys_per_client": 4,
       "tenant_dist": {"kind": "uniform"}}
WINDOW_S, STALL_AT_S, STALL_S, RATE = 1.5, 0.5, 0.5, 1000.0


def drive(server, open_loop: bool):
    """One window of MIX against the stalling server; (latencies in ms from
    the ops as run.py takes them, the clients' ops, open loop's result,
    window end)."""
    ref = checker.Reference()
    clients = [loadgen.MixClient(5, cid, MIX, 64, ref) for cid in range(8)]
    for c in clients:
        c.recording = True
    loop = loadgen.Loop("127.0.0.1", server.port, fine_timer=open_loop)
    res = None
    try:
        loop.open(clients)
        t0 = time.monotonic() + 0.05
        server.stall_at, server.stall_s = t0 + STALL_AT_S, STALL_S
        if open_loop:
            due = [t0 + o for o in loadgen.open_schedule(5, 0, 0, RATE,
                                                         WINDOW_S)]
            res = loop.run_open(due)
            assert res["given_up"] == []
        else:
            loop.run(t0, t0 + WINDOW_S)
    finally:
        loop.close()
    ops = [o for c in clients for o in c.ops]
    assert ops and all(o[3] for o in ops)
    ms = sorted((o[2] - o[1]) * 1e3 for o in ops)
    return ms, ops, res, t0 + WINDOW_S


def test_a_stall_shows_in_the_open_loops_tail_and_not_in_the_closed_loops():
    server = StallingServer()
    try:
        closed_ms, closed_ops, _, closed_t1 = drive(server, open_loop=False)
        open_ms, open_ops, res, t1 = drive(server, open_loop=True)
    finally:
        server.close()
    # closed: eight requests waited out the stall, of thousands
    assert len(closed_ms) > 1000
    assert run.percentile(closed_ms, 0.99) < 100
    assert closed_ms[-1] > STALL_S * 1e3 * 0.9
    # open: a third of the window's requests fell due while it stood still
    assert abs(len(open_ms) - RATE * WINDOW_S) < 0.15 * RATE * WINDOW_S
    assert run.percentile(open_ms, 0.99) > STALL_S * 1e3 * 0.8
    assert run.percentile(open_ms, 0.5) < 100
    # the generator says how late it sent, how often the pool of eight was
    # dry, and what was due and unanswered at the window's end
    assert len(res["late"]) == len(open_ops)
    assert res["pool_dry"] > 0.2 * RATE * STALL_S
    late = sorted(res["late"])
    assert late[0] >= 0 and run.percentile(late, 0.5) < 0.01
    assert late[-1] > STALL_S * 0.8
    stats = run.client_stats(open_ops, [], late, t1)
    assert stats["late_p99_ms"] > STALL_S * 1e3 * 0.5
    assert stats["think_p50_us"] is None
    assert stats["backlog_end"] == sum(1 for o in open_ops if o[2] > t1) < 20
    # (a closed loop: what its eight clients had in flight at the end)
    assert run.client_stats(closed_ops, [], [], closed_t1) == {
        "think_p50_us": None, "late_p99_ms": None,
        "backlog_end": sum(1 for o in closed_ops if o[2] > closed_t1)}
    assert sum(1 for o in closed_ops if o[2] > closed_t1) <= 8


def test_a_request_with_no_connection_for_5s_is_given_up(monkeypatch):
    """One connection, a server that never answers: the first request times
    out 5 s (here 0.3 s) after its send, the ones due behind it are given
    up unsent, and the loop ends."""
    monkeypatch.setattr(loadgen, "CLIENT_TIMEOUT_S", 0.3)
    server = StallingServer()
    server.stall_at, server.stall_s = time.monotonic(), 3600.0
    ref = checker.Reference()
    client = loadgen.MixClient(5, 0, MIX, 64, ref)
    client.recording = True
    loop = loadgen.Loop("127.0.0.1", server.port, fine_timer=True)
    try:
        loop.open([client])
        t0 = time.monotonic()
        due = [t0 + 0.01, t0 + 0.02, t0 + 0.03]
        res = loop.run_open(due)
        assert time.monotonic() - t0 < 3.0
    finally:
        loop.close()
        server.close()
    assert [o[2:] for o in client.ops] == [(None, False)]
    assert client.ops[0][1] == due[0]
    assert res["given_up"] == due[1:] and res["pool_dry"] == 2
    assert len(res["late"]) == 1


@pytest.mark.parametrize("theta", [0.99, 1.1])
def test_zipf_rank_frequencies(theta):
    groups, draws = 1000, 200_000
    z = loadgen.ZipfTenants(2**31 + 3, groups, theta)
    assert sorted(z.perm) == list(range(groups))
    assert z.perm != list(range(groups))
    rank_of = {t: r for r, t in enumerate(z.perm)}
    rng = __import__("random").Random(11)
    seen = [0] * groups
    for _ in range(draws):
        seen[rank_of[z.pick(rng)]] += 1
    norm = sum(1 / r ** theta for r in range(1, groups + 1))
    for r in range(1, 11):
        want = draws / r ** theta / norm
        assert abs(seen[r - 1] / want - 1) < 0.05, (r, seen[r - 1], want)


def test_zipf_comes_from_the_seed():
    mix = {**MIX, "tenant_dist": {"kind": "zipf", "theta": 0.99}}
    loadgen.validate_mix(mix)

    def draws(seed):
        ref = checker.Reference()
        c = loadgen.MixClient(seed, 3, mix, 500, ref,
                              loadgen.tenant_dist(seed, mix, 500))
        return [c.next()[1][1:3] for _ in range(200)]

    assert draws(7) == draws(7) and draws(7) != draws(8)
    assert loadgen.tenant_dist(7, MIX, 500) is None
