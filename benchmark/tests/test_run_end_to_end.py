"""The rest of a run, driven without the look for a chip (a CPU member at
G=8 through run.run_cell): a sound run comes out correct; the control (the
WAL's tail lost under the member) and a timed path broken underneath (a
front that acknowledges writes it drops, or alters a read's answer where it
passes) come out not correct; an open-loop mix (no cell has one yet: cell 1's
mix, offered by a Poisson schedule) goes through every phase the same way,
the traced run's SIGKILL among them. Slow: each case boots a member (~15 s
with a warm compile cache)."""
import json
import os
import socket
import socketserver
import threading

import loadgen
import pytest
import run

pytestmark = pytest.mark.skipif(
    os.environ.get("JAX_PLATFORMS", "").lower() != "cpu",
    reason="boots a member: run with JAX_PLATFORMS=cpu")


def read_request(rfile):
    head = b""
    while not head.endswith(b"\r\n\r\n"):
        line = rfile.readline()
        if not line:
            return None
        head += line
    length = 0
    for ln in head.split(b"\r\n"):
        if ln.lower().startswith(b"content-length:"):
            length = int(ln.split(b":")[1])
    return head, rfile.read(length) if length else b""


class BrokenFront:
    """A TCP front between the generator and the member. fault
    "lost_ack": every 7th PUT is acknowledged here and never forwarded.
    "stale_read": every 50th GET's value is altered on its way back."""

    def __init__(self, member_port: int, fault: str) -> None:
        outer = self
        self.count = 0
        self.lock = threading.Lock()

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                up = socket.create_connection(("127.0.0.1", member_port))
                buf = bytearray()
                try:
                    while True:
                        req = read_request(self.rfile)
                        if req is None:
                            return
                        head, body = req
                        with outer.lock:
                            outer.count += 1
                            n = outer.count
                        is_put = head.startswith(b"PUT")
                        if fault == "lost_ack" and is_put and n % 7 == 0:
                            key = head.split(b" ")[1].split(b"/v2/keys")[1]
                            doc = json.dumps({"action": "set", "node": {
                                "key": key.decode(),
                                "value": body.decode()[len("value="):]}})
                            self.wfile.write(
                                b"HTTP/1.1 200 OK\r\nContent-Length: %d"
                                b"\r\n\r\n%s" % (len(doc), doc.encode()))
                            continue
                        up.sendall(head + body)
                        while True:
                            parsed = loadgen.parse_response(buf)
                            if parsed:
                                break
                            data = up.recv(65536)
                            if not data:
                                return
                            buf += data
                        status, rbody, used = parsed
                        raw = bytes(buf[:used])
                        del buf[:used]
                        if (fault == "stale_read" and not is_put
                                and n % 50 == 0):
                            doc = json.loads(rbody)
                            doc["node"]["value"] = "0" * len(
                                doc["node"]["value"])
                            rbody = json.dumps(doc).encode()
                            raw = (b"HTTP/1.1 200 OK\r\nContent-Length: %d"
                                   b"\r\n\r\n%s" % (len(rbody), rbody))
                        self.wfile.write(raw)
                finally:
                    up.close()

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


OPEN = {"loop": "open", "arrival": "poisson", "rate": 300.0, "clients": 64}
CASES = [
    # workload, open loop, control, fault, correct, the number that must be
    # over 0
    ("mt1k.put256-c256", False, False, None, True, None),
    ("mt1k.put256-c256", False, True, None, False,
     "readback_mismatches_after_sigkill"),
    ("mt1k.put256-c256", False, False, "lost_ack", False,
     "readback_mismatches"),
    ("share12k5.qget-c256", False, False, "stale_read", False,
     "stale_quorum_reads_in_window"),
    ("share12k5.put256-c256", True, True, None, False,
     "readback_mismatches_after_sigkill"),
    ("share12k5.put256-c256", True, False, "lost_ack", False,
     "readback_mismatches"),
]


def offer_open_loop(monkeypatch):
    """The cell's own mix, its requests due on a schedule at 300 a second
    over a pool of 64 connections."""
    load_cell = run.load_cell

    def opened(workload):
        cell, cfg, mix = load_cell(workload)
        mix = {**{k: v for k, v in mix.items() if k != "start_spread_ms"},
               **OPEN}
        loadgen.validate_mix(mix)
        return cell, cfg, mix

    monkeypatch.setattr(run, "load_cell", opened)


@pytest.mark.parametrize("workload,open_loop,control,fault,correct,number",
                         CASES,
                         ids=["sound", "control_wal_tail_lost",
                              "front_drops_acked_writes",
                              "front_alters_reads",
                              "open_loop_control_wal_tail_lost",
                              "open_loop_front_drops_acked_writes"])
def test_run(workload, open_loop, control, fault, correct, number, capfd,
             monkeypatch):
    fronts = []

    def front(port):
        fronts.append(BrokenFront(port, fault))
        return fronts[-1].port

    if open_loop:
        offer_open_loop(monkeypatch)
    try:
        result = run.run_cell(workload, seed=2**31 + 11, seconds=3.0,
                              trace=False, groups_override=8,
                              require_tpu=False, control=control,
                              front=front if fault else None)
    finally:
        for f in fronts:
            f.close()
    assert result["correct"] is correct
    assert result["attempted"] > 100 and result["failed"] == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["metrics"]["setup_s"]["value"] > 0
    checks = {}
    for line in capfd.readouterr().out.splitlines():
        doc = json.loads(line)
        if "check" in doc:
            assert doc["limit"] == 0
            checks[doc["check"]] = doc["value"]
    assert result["compared"] == {k: {"value": v, "limit": 0}
                                  for k, v in checks.items()}
    if number is None:
        assert all(v == 0 for v in checks.values()), checks
    else:
        assert checks[number] > 0, checks
        assert all(v == 0 for k, v in checks.items() if k != number), checks


def test_an_open_loop_mix_goes_through_every_phase(capfd, monkeypatch):
    """Cell 1 at G=8 with its writes due at 300 a second, traced: a sound
    run is correct, SIGKILL and restart included; the end-to-end metrics are
    the cell's own, taken from the due times; the open loop's own numbers
    are on the `samples` line; gen_think_us, which has no meaning without a
    reply to wait for, is left out of the line and every other layer metric
    of the cell is read."""
    workload, seconds, rate = "share12k5.put256-c256", 4.0, OPEN["rate"]
    offer_open_loop(monkeypatch)
    result = run.run_cell(workload, seed=2**31 + 13, seconds=seconds,
                          trace=True, groups_override=8, require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "compared"]
    assert abs(result["attempted"] - rate * seconds) < 0.15 * rate * seconds
    assert set(result["compared"]) >= {"readback_mismatches_after_sigkill",
                                       "cross_tenant_leaks_after_sigkill"}
    lines = {}
    for line in capfd.readouterr().out.splitlines():
        doc = json.loads(line)
        if "phase" in doc:
            lines[doc["phase"]] = doc
    samples = lines["samples"]
    assert samples["rate"] == rate and samples["given_up"] == 0
    assert samples["think_samples"] == 0
    assert {"backlog_end", "pool_dry", "late_p50_ms",
            "late_max_ms"} <= set(samples)
    e2e = lines["end_to_end_of_traced_run"]["metrics"]
    assert set(e2e) == {"acked_ops_per_s", "ack_p50_ms", "write_ack_p99_ms",
                        "setup_s"}
    assert abs(e2e["acked_ops_per_s"]["value"] - rate) < 0.15 * rate
    assert 0 < e2e["ack_p50_ms"]["value"] <= e2e["write_ack_p99_ms"]["value"]
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        want = {m["name"] for m in json.load(f)["per_layer"]
                if workload in m.get("workloads", [workload])}
    # (lib/peaks.json has no peak for a CPU, so no share of a roofline)
    assert set(result["metrics"]) == want - {"gen_think_us", "step_roofline"}
