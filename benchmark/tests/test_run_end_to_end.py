"""The rest of a run, driven without the look for a chip (a CPU member at
G=8 through run.run_cell): a sound run comes out correct; the control (the
WAL's tail lost under the member) and a timed path broken underneath (a
front that acknowledges writes it drops, or alters a read's answer where it
passes) come out not correct. Slow: each case boots a member (~15 s with a
warm compile cache)."""
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


CASES = [
    # workload, control, fault, correct, the number that must be over 0
    ("mt1k.put256-c256", False, None, True, None),
    ("mt1k.put256-c256", True, None, False,
     "readback_mismatches_after_sigkill"),
    ("mt1k.put256-c256", False, "lost_ack", False, "readback_mismatches"),
    ("share12k5.qget-c256", False, "stale_read", False,
     "stale_quorum_reads_in_window"),
]


@pytest.mark.parametrize("workload,control,fault,correct,number", CASES,
                         ids=["sound", "control_wal_tail_lost",
                              "front_drops_acked_writes",
                              "front_alters_reads"])
def test_run(workload, control, fault, correct, number, capfd):
    fronts = []

    def front(port):
        fronts.append(BrokenFront(port, fault))
        return fronts[-1].port

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
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["metrics"]["setup_s"]["value"] > 0
    checks = {}
    for line in capfd.readouterr().out.splitlines():
        doc = json.loads(line)
        if "check" in doc:
            assert doc["limit"] == 0
            checks[doc["check"]] = doc["value"]
    if number is None:
        assert all(v == 0 for v in checks.values()), checks
    else:
        assert checks[number] > 0, checks
        assert all(v == 0 for k, v in checks.items() if k != number), checks
