"""chip_smoke.py and the process entry's compile cache, rehearsed on the
CPU: the smoke's parent stays off JAX, the whole sequence runs at a tiny G
and refuses to call a CPU run a success, `python -m etcd_tpu
--engine-groups ...` owns its compile cache without a script's help, and
JAX_COMPILATION_CACHE_DIR — where set — is the only cache directory."""
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def smoke_tree(tmp_path_factory):
    """A private copy of the tree's sources for the smoke to run in:
    chip_smoke.py runs ./build and watches its compile cache, and must do
    neither in the checkout the rest of the suite is running from (a .so
    appearing mid-session flips other tests' importorskip; other tests'
    compiles would land in the cache it watches)."""
    dst = tmp_path_factory.mktemp("smoke") / "tree"
    shutil.copytree(REPO, dst, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "coverage", "__pycache__",
        "*.so", ".pytest_cache", "tests", "docs"))
    return str(dst)


def _smoke(tree, *args):
    return subprocess.run(
        [sys.executable, os.path.join(tree, "chip_smoke.py"), *args],
        env=_env(PYTHONPATH=tree,
                 JAX_COMPILATION_CACHE_DIR=os.path.join(tree, "cache")),
        capture_output=True, text=True, timeout=600)


def test_chip_smoke_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith(('jax.', 'jaxlib', 'etcd_tpu.ops', "
            "'etcd_tpu.server.engine', 'etcd_tpu.utils.platform'))]; "
            "assert not bad, bad" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env=_env(),
                   timeout=60)


def test_chip_smoke_cpu_rehearsal_says_not_ok(smoke_tree):
    """JAX_PLATFORMS=cpu --groups 8: every phase runs and passes, and the
    last line still says "ok": false with the device it really ran on."""
    r = _smoke(smoke_tree, "--groups", "8")
    lines = [json.loads(ln) for ln in r.stdout.strip().splitlines()]
    assert r.returncode == 1, r.stderr[-3000:]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}, r.stderr[-3000:]
    phases = [ln.get("phase") for ln in lines[:-1]]
    for want in ("build", "one-chip:boot-cold", "one-chip:writes", "cas",
                 "one-chip:reads", "one-chip:replay-reads",
                 "one-chip:boot-warm"):
        assert want in phases, (want, phases)
    warm = lines[phases.index("one-chip:boot-warm")]
    assert warm["warm_boot_hit_cache"] and warm["mask_repairs"] == 0


def test_chip_smoke_without_groups_fails_fast_off_tpu(smoke_tree):
    """As the driver runs it (no arguments) where there is no TPU: a
    non-zero exit and no success line, without serving the full size."""
    r = _smoke(smoke_tree)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def _cache_dir_seen_by(env) -> str:
    code = ("from etcd_tpu.utils.platform import enable_compile_cache; "
            "import jax; d = enable_compile_cache(); "
            "assert d == jax.config.jax_compilation_cache_dir, d; print(d)")
    r = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       capture_output=True, text=True, timeout=120)
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_dir_env_wins_else_checkout(tmp_path):
    x = str(tmp_path / "x")
    assert _cache_dir_seen_by(_env(JAX_COMPILATION_CACHE_DIR=x)) == x
    assert os.path.isdir(x)
    assert _cache_dir_seen_by(_env()) == os.path.join(REPO, ".jax_cache")


def test_engine_entry_point_populates_compile_cache(tmp_path):
    """`python -m etcd_tpu --engine-groups 4` alone — no script, no
    conftest — compiles into the cache directory and reports its device."""
    from etcd_tpu.tools.functional_tester import _free_ports
    cache = tmp_path / "cache"
    (port,) = _free_ports(1)
    base = f"http://127.0.0.1:{port}"
    p = subprocess.Popen(
        [sys.executable, "-m", "etcd_tpu", "--engine-groups", "4",
         "--engine-peers", "3", "--data-dir", str(tmp_path / "d"),
         "--listen-client-urls", base],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache)),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 240
        st = {}
        while st.get("groups_with_leader") != 4:
            assert time.time() < deadline and p.poll() is None, st
            try:
                with urllib.request.urlopen(base + "/engine/status",
                                            timeout=5) as r:
                    st = json.loads(r.read())
            except OSError:
                time.sleep(0.5)
        assert (st["platform"], st["device_kind"], st["device_count"]) == (
            "cpu", "cpu", 1)
        assert st["device_rows"] == {"0": 4} and st["mask_repairs"] == 0
        entries = [f for f in os.listdir(cache) if f.endswith("-cache")]
        assert any(f.startswith("jit_step_routed") for f in entries), entries
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=60) == 0
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()


def test_mesh_flag_refused_on_one_device(tmp_path):
    """A 1x1 mesh shards nothing: the flag is refused at the flag level."""
    r = subprocess.run(
        [sys.executable, "-m", "etcd_tpu", "--engine-groups", "4",
         "--engine-mesh-peers-axis", "1", "--data-dir", str(tmp_path / "d")],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert "engine-mesh-peers-axis" in r.stderr and "Traceback" not in r.stderr
