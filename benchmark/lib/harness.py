"""The member process and the helpers around it.

Copied from chip_smoke.py (PR 21, proven on the chip): `Member`, `http`,
`timed_out`, `Deadline`, `build_native`, `device_of`. A copy, because later
PRs may change chip_smoke.py and may not change the yardstick. Differences:
the member is started through benchmark/lib/member.py (the same
`etcd_tpu.etcdmain.main`, plus the profiler control thread) and takes its
command line from the configuration file.

Nothing here imports JAX: the member owns the chip.
"""
from __future__ import annotations

import glob
import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

LIB = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(LIB)
ROOT = os.path.dirname(BENCH)
NATIVE = ("walcodec", "storecore", "ingresscore", "frontcore")
TRACE_CTL_ENV = "ETCD_BENCH_TRACE_CTL"


class BenchFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


class Deadline:
    """A phase's time limit, capped by the whole run's: a hang becomes a
    failure."""

    run_end = float("inf")      # set once by the runner

    def __init__(self, phase: str, seconds: float) -> None:
        self.phase = phase
        self.seconds = seconds
        self.t0 = time.monotonic()
        self.end = min(self.t0 + seconds, Deadline.run_end)

    def left(self) -> float:
        left = self.end - time.monotonic()
        if left <= 0:
            raise BenchFailure(f"{self.phase}: deadline exceeded "
                               f"({self.seconds}s for the phase)")
        return left

    def elapsed(self) -> float:
        return time.monotonic() - self.t0


_FORM = {"Content-Type": "application/x-www-form-urlencoded"}


def http(method: str, url: str, form: dict | None, timeout: float):
    """(status, parsed JSON body). HTTP error statuses are answers, not
    exceptions; transport errors propagate and fail the phase."""
    data = urllib.parse.urlencode(form).encode() if form is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers=_FORM if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def http_text(url: str, timeout: float) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def timed_out(status: int, body: dict) -> bool:
    """The engine's own 5 s request timeout (errorCode 300): what a client
    sees while the round loop compiles or loads a step variant."""
    return (status >= 500 and body.get("errorCode") == 300
            and "timed out" in body.get("cause", ""))


def cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def native_built() -> bool:
    return all(glob.glob(os.path.join(ROOT, "etcd_tpu", "native", n + "*.so"))
               for n in NATIVE)


def build_native() -> float:
    """./build if a native module is missing; seconds it took (0.0 when
    nothing was to build)."""
    if native_built():
        return 0.0
    dl = Deadline("build", 180)
    build = os.path.join(ROOT, "build")
    check(os.path.exists(build), "build: ./build is not here — the "
                                 "benchmark runs from the root of a checkout")
    r = subprocess.run([build], cwd=ROOT, capture_output=True, text=True,
                       timeout=dl.left())
    check(r.returncode == 0, f"build: ./build failed rc={r.returncode}\n"
                             f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    check(native_built(), "build: native modules missing after ./build")
    return dl.elapsed()


def device_of(st: dict) -> dict:
    return {"platform": st["platform"], "kind": st["device_kind"],
            "count": st["device_count"],
            "memory_peak_bytes": st.get("device_peak_bytes")}


def with_groups(cli: list, groups: int | None) -> list:
    """The configuration's CLI with --engine-groups replaced (rehearsal)."""
    cli = [str(a) for a in cli]
    if groups is not None:
        i = cli.index("--engine-groups")
        cli[i + 1] = str(groups)
    return cli


def cli_value(cli: list, flag: str) -> int:
    return int(cli[cli.index(flag) + 1])


class Member:
    """One served member: `python benchmark/lib/member.py <cli> --data-dir D
    --listen-client-urls URL`. The only process that touches JAX."""

    def __init__(self, work: str, cli: list, trace_ctl: str | None) -> None:
        self.cli = cli
        self.groups = cli_value(cli, "--engine-groups")
        self.trace_ctl = trace_ctl
        self.data_dir = os.path.join(work, "member.data")
        self.log_path = os.path.join(work, "member.log")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: subprocess.Popen | None = None
        self.boots = 0

    def spawn(self) -> None:
        check(self.proc is None, "member: previous process not reaped")
        cmd = [sys.executable, os.path.join(LIB, "member.py"), *self.cli,
               "--data-dir", self.data_dir,
               "--listen-client-urls", self.base]
        env = dict(os.environ)       # nothing here selects a platform
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
        env.pop(TRACE_CTL_ENV, None)
        if self.trace_ctl and self.boots == 0:
            env[TRACE_CTL_ENV] = self.trace_ctl
        self.boots += 1
        with open(self.log_path, "ab") as logf:
            logf.write(f"--- boot {self.boots}: {' '.join(cmd)}\n".encode())
            logf.flush()
            self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                         stdout=logf, stderr=logf)

    def status(self, timeout: float) -> dict:
        code, body = http("GET", self.base + "/engine/status", None, timeout)
        check(code == 200, f"member: /engine/status answered {code}")
        return body

    def metrics_text(self, timeout: float = 30.0) -> str:
        return http_text(self.base + "/metrics", timeout)

    def wait_status(self, dl: Deadline, ready) -> dict:
        """Poll /engine/status until ready(status). The listener not being
        up yet is the one expected transport error; a dead process is
        fatal at once."""
        while True:
            left = dl.left()
            rc = self.proc.poll()
            check(rc is None, f"{dl.phase}: member exited rc={rc}")
            try:
                st = self.status(min(30.0, left))
            except (urllib.error.URLError, ConnectionError, socket.timeout):
                time.sleep(0.25)
                continue
            if ready(st):
                return st
            time.sleep(0.5)

    def reap(self, sig: int, dl: Deadline) -> int:
        self.proc.send_signal(sig)
        try:
            rc = self.proc.wait(timeout=dl.left())
        except subprocess.TimeoutExpired:
            raise BenchFailure(f"{dl.phase}: member ignored signal {sig}")
        self.proc = None
        return rc

    def destroy(self) -> None:
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait()
            self.proc = None

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode(errors="replace")
        except OSError:
            return ""
