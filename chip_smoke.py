#!/usr/bin/env python3
"""chip_smoke.py — the served multi-tenant engine, end to end, on the chip.

What it drives: the normal entry point, `python -m etcd_tpu --engine-groups G
--engine-peers 5 --engine-window 32 --data-dir D --listen-client-urls URL`
(EngineServer -> MultiEngine round loop -> jitted kernel.step_routed_* on the
device -> readback -> engine WAL with fsync on, as shipped -> applier -> ack),
over HTTP at /tenants/{g}/v2/keys/..., and nothing else.

Size: G = 12,500 groups x P = 5 peers, window 32, hops 3 (the EngineConfig
default), fsync on — ONE chip's share of BASELINE.json config 4 (100k groups
x 5 peers on a v5e-8). It is not shrunk for the chip. `--groups` exists for
the CPU rehearsal only (JAX_PLATFORMS=cpu python chip_smoke.py --groups 8),
and a run that did not happen on a TPU never reports success: its last line
says "ok": false with the device it really ran on, and the exit code is 1.

Sequence (every step is an assertion, every phase has a deadline, the first
failure is fatal): ./build the native modules; boot the member cold and wait
for every group to elect; check the device through GET /engine/status
(platform, device count, state rows per device); seeded writes (--seed) to
the first, last, middle and shard-boundary tenants and 300 random ones, every
fourth several keys deep; read each key back plainly and with ?quorum=true
and compare with a plain dict fed the same operations; a CAS winner/loser
pair; tenant isolation; mask_repairs == 0; SIGKILL, reap, restart on the same
data dir (engine WAL replay, compile cache warm), read everything again;
SIGTERM exits 0.

`--chips 4` runs ONLY the sharded path and what it is compared with: one
member with --engine-groups 50000 --engine-mesh-peers-axis 1 (groups axis 4;
P=5 is odd, so a peers axis > 1 has no sourced deployment and the all_to_all
variant stays with the CPU tests), which must hold G/4 rows on each of four
distinct devices and pass the same sequence; then, after it is reaped, an
unsharded G=12,500 member fed the same seeded operations restricted to
tenants 0..12,499, which must give the same answers. The mesh path runs with
compact readback off: every round reads back the full O(G*P*W) state, whose
size is printed.

One chip belongs to one process. This parent never imports JAX (nor
etcd_tpu.ops / etcd_tpu.server.engine / etcd_tpu.utils.platform): the member
owns the chip, and the parent learns the device from /engine/status. Members
run strictly one after another. Nothing here selects a platform.

Output: one JSON object per line; the LAST line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from importlib import metadata

ROOT = os.path.dirname(os.path.abspath(__file__))
G_ONE_CHIP = 12_500        # one chip's share of 100k groups on a v5e-8
PEERS, WINDOW, HOPS = 5, 32, 3
N_RANDOM_TENANTS = 300
CLIENT_THREADS = 32
NATIVE = ("walcodec", "storecore", "ingresscore")

# Deadlines (seconds). A cold member compiles one step variant before the
# first election and the read variant at the first quorum read. One variant
# at G=12,500 took ~85 s to compile for a v5e in the sandbox (~190 s with
# four compiles sharing it) and ~30-45 s on the chip's host, where the whole
# one-chip run took 132 s and the four-chip run 328 s (CHANGES.md, PR 21).
DL_BUILD = 180
DL_BOOT_COLD = 480
DL_BOOT_WARM = 300
DL_READ_VARIANT = 480
DL_TRAFFIC = 240
DL_EXIT = 60
DL_TOTAL = {1: 1150, 4: 2700}

_T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Deadline:
    """A phase's time limit, capped by the whole run's: a hang becomes a
    failure."""

    run_end = float("inf")      # set once by main()

    def __init__(self, phase: str, seconds: float) -> None:
        self.phase = phase
        self.seconds = seconds
        self.t0 = time.monotonic()
        self.end = min(self.t0 + seconds, Deadline.run_end)

    def left(self) -> float:
        left = self.end - time.monotonic()
        if left <= 0:
            raise SmokeFailure(
                f"{self.phase}: deadline exceeded ({self.seconds}s for the "
                f"phase, {time.monotonic() - _T0:.0f}s into the run)")
        return left

    def elapsed(self) -> float:
        return round(time.monotonic() - self.t0, 2)


# ---------------------------------------------------------------------------
# HTTP (urllib only)
# ---------------------------------------------------------------------------

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


def timed_out(status: int, body: dict) -> bool:
    """The engine's own 5 s request timeout (errorCode 300): what a client
    sees while the round loop compiles a step variant. Protocol-level
    retryable for idempotent requests, like any etcd server error."""
    return (status >= 500 and body.get("errorCode") == 300
            and "timed out" in body.get("cause", ""))


# ---------------------------------------------------------------------------
# The member process (the only process that touches JAX)
# ---------------------------------------------------------------------------

class Member:
    def __init__(self, work: str, name: str, groups: int, mesh: bool) -> None:
        self.name, self.groups, self.mesh = name, groups, mesh
        self.data_dir = os.path.join(work, name + ".data")
        self.log_path = os.path.join(work, name + ".log")
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: subprocess.Popen | None = None
        self.boots = 0

    def spawn(self) -> None:
        check(self.proc is None, f"{self.name}: previous process not reaped")
        cmd = [sys.executable, "-m", "etcd_tpu",
               "--engine-groups", str(self.groups),
               "--engine-peers", str(PEERS),
               "--engine-window", str(WINDOW),
               "--data-dir", self.data_dir,
               "--listen-client-urls", self.base]
        if self.mesh:
            cmd += ["--engine-mesh-peers-axis", "1"]
        env = dict(os.environ)       # nothing here selects a platform
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
        self.boots += 1
        with open(self.log_path, "ab") as logf:
            logf.write(f"--- boot {self.boots}: {' '.join(cmd)}\n".encode())
            logf.flush()
            self.proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                         stdout=logf, stderr=logf)

    def status(self, timeout: float) -> dict:
        code, body = http("GET", self.base + "/engine/status", None, timeout)
        check(code == 200, f"{self.name}: /engine/status answered {code}")
        return body

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
                time.sleep(0.5)
                continue
            if ready(st):
                return st
            time.sleep(1.0)

    def reap(self, sig: int, dl: Deadline) -> int:
        self.proc.send_signal(sig)
        try:
            rc = self.proc.wait(timeout=dl.left())
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{dl.phase}: member ignored signal {sig}")
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


# ---------------------------------------------------------------------------
# Seeded operations and the plain reference
# ---------------------------------------------------------------------------

def make_ops(seed: int, groups: int):
    """The seeded write sequence: [(tenant, key, value)]. Tenants: first,
    last, middle, both sides of every quarter boundary (the shard edges
    of a groups-axis-4 mesh), and N_RANDOM_TENANTS random ones. Every
    tenant writes the SAME key `smoke/shared` with its own value (tenant
    isolation); every fourth goes several keys deep, nested dirs and an
    overwrite included."""
    rng = random.Random(seed)
    q = max(1, groups // 4)
    fixed = [0, groups - 1, groups // 2,
             q - 1, q, 2 * q - 1, 3 * q - 1, 3 * q]
    rand = rng.sample(range(groups), min(N_RANDOM_TENANTS, groups))
    tenants = [t for t in dict.fromkeys(fixed + rand) if 0 <= t < groups]

    def val(tag: str) -> str:
        return f"{tag}-{rng.getrandbits(64):016x}"

    ops = []
    for i, t in enumerate(tenants):
        ops.append((t, "smoke/shared", val(f"t{t}")))
        if i % 4 == 0:
            for j in range(3):
                ops.append((t, f"smoke/deep/d{j}/leaf", val(f"t{t}d{j}")))
            ops.append((t, "smoke/deep/d0/leaf", val(f"t{t}again")))
    return ops


def reference(ops) -> dict:
    """The plain reference: a dict fed the same operations in order."""
    model = {}
    for t, key, value in ops:
        model[(t, key)] = value
    return model


def run_pool(dl: Deadline, fn, items) -> list:
    with ThreadPoolExecutor(CLIENT_THREADS) as pool:
        futs = [pool.submit(fn, it) for it in items]
        return [f.result(timeout=dl.left()) for f in futs]


def key_url(m: Member, t: int, key: str, **params) -> str:
    q = ("?" + urllib.parse.urlencode(params)) if params else ""
    return f"{m.base}/tenants/{t}/v2/keys/{key}{q}"


def put(m: Member, dl: Deadline, t: int, key: str, value: str) -> int:
    """One acked, idempotent set; returns how often the engine's request
    timeout made us resend it."""
    retries = 0
    while True:
        code, body = http("PUT", key_url(m, t, key), {"value": value},
                          min(60.0, dl.left()))
        if timed_out(code, body):
            retries += 1
            continue
        check(code in (200, 201) and body["node"]["value"] == value,
              f"{dl.phase}: PUT t={t} {key} answered {code} {body}")
        return retries


def get(m: Member, dl: Deadline, t: int, key: str, quorum: bool):
    """(value, retries): one read, plain or linearizable."""
    params = {"quorum": "true"} if quorum else {}
    retries = 0
    while True:
        code, body = http("GET", key_url(m, t, key, **params), None,
                          min(60.0, dl.left()))
        if timed_out(code, body):
            retries += 1
            continue
        check(code == 200, f"{dl.phase}: GET t={t} {key} quorum={quorum} "
                           f"answered {code} {body}")
        return body["node"]["value"], retries


def write_all(m: Member, ops, phase: str) -> dict:
    """Tenants in parallel, each tenant's writes in order."""
    dl = Deadline(phase, DL_TRAFFIC)
    by_tenant: dict = {}
    for t, key, value in ops:
        by_tenant.setdefault(t, []).append((key, value))

    def one_tenant(item) -> int:
        t, kvs = item
        return sum(put(m, dl, t, k, v) for k, v in kvs)

    retries = sum(run_pool(dl, one_tenant, by_tenant.items()))
    return {"phase": phase, "writes_acked": len(ops),
            "tenants": len(by_tenant), "resent_after_timeout": retries,
            "seconds": dl.elapsed()}


def read_all(m: Member, model: dict, phase: str):
    """Every key, plainly and through the quorum read plane, against the
    reference. The first quorum read compiles (or loads) the read-step
    variant, so it runs alone first under its own deadline. Returns the
    summary line and what the member answered, {(tenant, key): value}."""
    (t0, k0), v0 = next(iter(model.items()))
    dl = Deadline(phase + ":read-variant", DL_READ_VARIANT)
    got, first_retries = get(m, dl, t0, k0, quorum=True)
    check(got == v0, f"{dl.phase}: t={t0} {k0} quorum read {got!r} != {v0!r}")
    first_s = dl.elapsed()

    dl = Deadline(phase, DL_TRAFFIC)

    def one_key(item):
        (t, key), want = item
        plain, r1 = get(m, dl, t, key, quorum=False)
        quor, r2 = get(m, dl, t, key, quorum=True)
        check(plain == want, f"{phase}: t={t} {key} plain read {plain!r} "
                             f"!= reference {want!r}")
        check(quor == want, f"{phase}: t={t} {key} quorum read {quor!r} "
                            f"!= reference {want!r}")
        return (t, key), quor, r1 + r2

    got = run_pool(dl, one_key, model.items())
    summary = {"phase": phase, "keys": len(model),
               "reads_checked": 2 * len(model),
               "first_quorum_read_s": first_s,
               "resent_after_timeout": first_retries + sum(r for *_, r in got),
               "seconds": dl.elapsed()}
    return summary, {k: v for k, v, _ in got}


def cas_pair(m: Member, model: dict) -> dict:
    """Winner 200, loser 412 / errorCode 101, value is the winner's. Not
    retried: a CAS is not idempotent, so a timeout here is a failure."""
    dl = Deadline("cas", DL_TRAFFIC)
    t, key = m.groups // 2, "smoke/cas"
    put(m, dl, t, key, "v0")
    url = key_url(m, t, key, prevValue="v0")
    code, body = http("PUT", url, {"value": "winner"}, dl.left())
    check(code == 200 and body["node"]["value"] == "winner",
          f"cas: winner answered {code} {body}")
    code, body = http("PUT", url, {"value": "loser"}, dl.left())
    check(code == 412 and body.get("errorCode") == 101,
          f"cas: loser answered {code} {body}")
    model[(t, key)] = "winner"
    return {"phase": "cas", "winner": 200, "loser": [412, 101]}


# ---------------------------------------------------------------------------
# Compile cache, versions, native modules — all without importing JAX
# ---------------------------------------------------------------------------

def cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def step_cache_entries() -> set:
    """Persistent-cache entries of the serving step variants."""
    return {os.path.basename(p) for p in glob.glob(
        os.path.join(cache_dir(), "jit_step_routed*-cache"))}


def versions() -> dict:
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def build_native() -> list:
    dl = Deadline("build", DL_BUILD)
    build = os.path.join(ROOT, "build")
    check(os.path.exists(build), "build: ./build is not here — chip_smoke.py "
                                 "runs from the root of a checkout")
    r = subprocess.run([build], cwd=ROOT, capture_output=True, text=True,
                       timeout=dl.left())
    check(r.returncode == 0, f"build: ./build failed rc={r.returncode}\n"
                             f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    # ./build ends by importing each module and asserting it loaded; the
    # member uses the same interpreter and tree, so it cannot quietly take
    # the pure-Python store/codec.
    built = [n for n in NATIVE
             if glob.glob(os.path.join(ROOT, "etcd_tpu", "native", n + "*.so"))]
    check(built == list(NATIVE), f"build: native modules missing: {built}")
    return built


# ---------------------------------------------------------------------------
# One member through the whole sequence
# ---------------------------------------------------------------------------

def device_of(st: dict) -> dict:
    return {"platform": st["platform"], "kind": st["device_kind"],
            "count": st["device_count"]}


def check_placement(m: Member, st: dict, chips: int, rehearsal: bool) -> None:
    if not rehearsal:
        check(st["platform"] == "tpu", f"{m.name}: platform {st['platform']}")
    check(st["device_count"] == chips,
          f"{m.name}: {st['device_count']} devices visible, want {chips}")
    if m.mesh:
        # device_rows is keyed by device id, so its length counts
        # distinct devices.
        check(len(st["device_rows"]) == chips
              and set(st["device_rows"].values()) == {m.groups // chips},
              f"{m.name}: state rows per device {st['device_rows']}, want "
              f"{m.groups // chips} on each of {chips} distinct devices")
    else:
        check(list(st["device_rows"].values()) == [m.groups],
              f"{m.name}: state rows per device {st['device_rows']}, want "
              f"all {m.groups} on one device")


def run_member(m: Member, ops, chips: int, rehearsal: bool,
               fail_fast_off_tpu: bool):
    """Boot cold, serve the seeded sequence, kill, boot warm, serve it
    again, exit cleanly. Returns (device, answers) — what the restarted
    member served for every key — or (device, None) when
    fail_fast_off_tpu stopped the run on a non-TPU backend."""
    model = reference(ops)
    entries0 = step_cache_entries()

    # -- boot 1 ------------------------------------------------------------
    dl = Deadline(m.name + ":boot-cold", DL_BOOT_COLD)
    m.spawn()
    st = m.wait_status(dl, lambda s: True)
    device = device_of(st)
    if st["platform"] != "tpu" and fail_fast_off_tpu:
        return device, None
    st = m.wait_status(dl, lambda s: s["groups_with_leader"] == s["groups"])
    cold_s = dl.elapsed()
    check(st["groups"] == m.groups, f"{m.name}: serves {st['groups']} groups")
    check_placement(m, st, chips, rehearsal)
    emit(phase=m.name + ":boot-cold", groups=m.groups, peers=PEERS,
         window=WINDOW, hops=HOPS, mesh=m.mesh, device=device,
         device_rows=st["device_rows"], seconds_to_all_leaders=cold_s,
         step_cache_entries_before=len(entries0))

    emit(**write_all(m, ops, m.name + ":writes"))
    emit(**cas_pair(m, model))
    summary, answers = read_all(m, model, m.name + ":reads")
    emit(**summary)
    # Tenant isolation: the same key in the first and the last tenant
    # holds two values, each tenant's own (read_all held both to the
    # reference; the reference values differ by construction).
    first, last = (0, "smoke/shared"), (m.groups - 1, "smoke/shared")
    check(answers[first] != answers[last]
          and answers[first].startswith("t0-")
          and answers[last].startswith(f"t{m.groups - 1}-"),
          f"isolation: {answers[first]!r} / {answers[last]!r}")
    st = m.status(30.0)
    check(st["mask_repairs"] == 0,
          f"{m.name}: mask_repairs={st['mask_repairs']} — the device "
          "peer_mask was corrupted and repaired (donation?)")
    entries1 = step_cache_entries()
    check(len(entries1) >= 2, f"{m.name}: compile cache {cache_dir()} holds "
                              f"{len(entries1)} step entries after a cold boot")
    # The round loop with no client traffic, on the host's clock (the
    # engine's own round_ms_ewma still carries the compile stalls).
    st1, t_idle = m.status(30.0), time.monotonic()
    time.sleep(3.0)
    st2, idle_s = m.status(30.0), time.monotonic() - t_idle
    check(st2["round"] > st1["round"], f"{m.name}: the round loop stalled")
    emit(phase=m.name + ":after-cold", mask_repairs=st["mask_repairs"],
         acked_requests=st["acked_requests"], rounds=st["round"],
         idle_round_ms=round(1000 * idle_s / (st2["round"] - st1["round"]), 2),
         device_peak_bytes=st.get("device_peak_bytes"),
         step_cache_entries=len(entries1))

    # -- SIGKILL, reap, boot 2 on the same data dir ---------------------------
    rc = m.reap(signal.SIGKILL, Deadline(m.name + ":sigkill", DL_EXIT))
    check(rc == -signal.SIGKILL, f"{m.name}: SIGKILL gave rc={rc}")
    dl = Deadline(m.name + ":boot-warm", DL_BOOT_WARM)
    m.spawn()
    st = m.wait_status(dl, lambda s: s["groups_with_leader"] == s["groups"])
    warm_s = dl.elapsed()
    check_placement(m, st, chips, rehearsal)
    summary, answers = read_all(m, model, m.name + ":replay-reads")
    emit(**summary)
    dlp = Deadline(m.name + ":post-restart-write", DL_TRAFFIC)
    put(m, dlp, m.groups - 1, "smoke/after-restart", "served")
    got, _ = get(m, dlp, m.groups - 1, "smoke/after-restart", quorum=True)
    check(got == "served", f"{m.name}: post-restart write read back {got!r}")
    st = m.status(30.0)
    check(st["mask_repairs"] == 0,
          f"{m.name}: mask_repairs={st['mask_repairs']} after restart")
    entries2 = step_cache_entries()
    check(entries2 == entries1,
          f"{m.name}: the warm boot compiled step variants again: "
          f"{sorted(entries2 - entries1)}")
    emit(phase=m.name + ":boot-warm", seconds_to_all_leaders=warm_s,
         cold_seconds_to_all_leaders=cold_s, compile_cache_dir=cache_dir(),
         warm_boot_hit_cache=True, new_step_cache_entries=0,
         mask_repairs=st["mask_repairs"],
         device_peak_bytes=st.get("device_peak_bytes"))

    rc = m.reap(signal.SIGTERM, Deadline(m.name + ":sigterm", DL_EXIT))
    check(rc == 0, f"{m.name}: SIGTERM gave rc={rc}")
    return device, answers


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path and its one-chip "
                         "comparison (run by hand; the driver never does)")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--groups", type=int, default=None,
                    help="CPU rehearsal only (needs JAX_PLATFORMS=cpu); "
                         "with --chips 4 the sharded member's G")
    args = ap.parse_args()

    Deadline.run_end = _T0 + DL_TOTAL[args.chips]
    rehearsal = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    check(args.groups is None or rehearsal,
          "--groups is for the CPU rehearsal (JAX_PLATFORMS=cpu); the chip "
          "run is not shrunk")
    groups = args.groups or G_ONE_CHIP * args.chips
    check(groups % args.chips == 0 and groups >= 8,
          "--groups must be >= 8 and divide by --chips")

    emit(phase="start", chips=args.chips, seed=args.seed, groups=groups,
         peers=PEERS, window=WINDOW, hops=HOPS, fsync=True,
         versions=versions(), compile_cache_dir=cache_dir(),
         rehearsal_on_cpu=rehearsal)
    emit(phase="build", native_modules=build_native())

    work = tempfile.mkdtemp(prefix="chip_smoke-")
    members = []
    try:
        ops = make_ops(args.seed, groups)
        mesh = args.chips == 4
        m = Member(work, "mesh-4" if mesh else "one-chip", groups, mesh)
        members.append(m)
        device, answers = run_member(m, ops, args.chips, rehearsal,
                                     fail_fast_off_tpu=args.groups is None)
        if mesh and answers is not None:
            # What it is compared with: one shard's worth on one device,
            # the same seeded operations restricted to it.
            shard = groups // 4
            c = Member(work, "one-shard", shard, mesh=False)
            members.append(c)
            _, c_answers = run_member(
                c, [op for op in ops if op[0] < shard], args.chips,
                rehearsal, fail_fast_off_tpu=False)
            # (the CAS key lives at each member's own middle tenant)
            same = {k: v for k, v in answers.items()
                    if k[0] < shard and k[1] != "smoke/cas"}
            c_same = {k: v for k, v in c_answers.items()
                      if k[1] != "smoke/cas"}
            check(same == c_same and same,
                  "comparison: the sharded member and the one-shard "
                  "member disagree on tenants below %d" % shard)
            emit(phase="comparison", tenants_below=shard,
                 keys_compared=len(same), same_answers=True)
    except BaseException as e:
        for mm in members:
            tail = mm.log_tail()
            if tail:
                print(f"--- {mm.name} log tail ---\n{tail}", file=sys.stderr)
        print(f"chip_smoke: FAILED: {e!r}", file=sys.stderr, flush=True)
        raise
    finally:
        keep = os.path.join(ROOT, "chiprun_out", "chip_smoke")
        os.makedirs(keep, exist_ok=True)
        for mm in members:
            mm.destroy()
            if os.path.exists(mm.log_path):   # git-ignored; for the builder
                shutil.copy(mm.log_path, keep)
        shutil.rmtree(work, ignore_errors=True)

    ok = device["platform"] == "tpu" and answers is not None
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
