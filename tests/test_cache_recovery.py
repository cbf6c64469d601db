"""The kernel cache's startup crash-recovery sweep (orphaned tmp files,
stale locks, the quarantine byte budget) and cross-process cache
contention."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import pipeline
from repro.pipeline import cache as C

SRC = Path(pipeline.__file__).resolve().parents[2]


# ---------------------------------------------------------------------------
# cache crash-recovery sweep
# ---------------------------------------------------------------------------

def test_recovery_sweep_removes_dead_writer_tmp_files(tmp_path):
    # a pid guaranteed dead: a subprocess that already exited
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    dead = tmp_path / f"abc.json.{p.pid}.tmp"
    dead.write_text("half-written plan from a crashed writer")
    live = tmp_path / f"def.json.{os.getpid()}.tmp"
    live.write_text("an in-flight write by a live process")
    foreign_old = tmp_path / "weird.tmp"  # no pid: age decides
    foreign_old.write_text("x")
    os.utime(foreign_old, (0, 0))
    with pytest.warns(RuntimeWarning, match="orphaned tmp"):
        kc = C.KernelCache(root=tmp_path)
    assert kc.stats.recovered_tmp == 2
    assert not dead.exists() and not foreign_old.exists()
    assert live.exists()  # never races a live writer


def test_recovery_sweep_removes_stale_unheld_lock(tmp_path):
    lock = tmp_path / ".lock"
    lock.write_text("")
    os.utime(lock, (0, 0))  # ancient and nobody holds it
    with pytest.warns(RuntimeWarning, match="stale lock"):
        kc = C.KernelCache(root=tmp_path)
    assert kc.stats.stale_locks == 1 and not lock.exists()


def test_recovery_sweep_spares_a_held_lock(tmp_path):
    import fcntl
    lock = tmp_path / ".lock"
    lock.write_text("")
    os.utime(lock, (0, 0))
    fd = os.open(str(lock), os.O_RDWR)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)  # a live writer holds it
        kc = C.KernelCache(root=tmp_path)
        assert kc.stats.stale_locks == 0 and lock.exists()
    finally:
        os.close(fd)


def test_quarantine_capped_at_byte_budget_oldest_first(tmp_path,
                                                       monkeypatch):
    monkeypatch.setenv("REPRO_QUARANTINE_MAX_BYTES", "100")
    qdir = tmp_path / "quarantine"
    qdir.mkdir(parents=True)
    for name, size, mtime in (("old.json", 60, 1000.0),
                              ("mid.json", 60, 2000.0),
                              ("new.json", 30, 3000.0)):
        f = qdir / name
        f.write_bytes(b"x" * size)
        os.utime(f, (mtime, mtime))
    with pytest.warns(RuntimeWarning, match="quarantine"):
        kc = C.KernelCache(root=tmp_path)
    assert kc.stats.quarantine_evicted == 1
    assert sorted(p.name for p in qdir.iterdir()) == ["mid.json",
                                                      "new.json"]


def test_recovery_sweep_is_silent_on_a_clean_cache(tmp_path, recwarn):
    kc = C.KernelCache(root=tmp_path)  # dir does not even exist yet
    st = kc.stats
    assert (st.recovered_tmp, st.stale_locks, st.quarantine_evicted) == \
        (0, 0, 0)
    mem = C.KernelCache(disk=False)  # memory-only caches never sweep
    assert mem.stats.recovered_tmp == 0
    assert not [w for w in recwarn.list
                if "kernel cache" in str(w.message)]


# ---------------------------------------------------------------------------
# cross-process cache contention
# ---------------------------------------------------------------------------

_CONTENTION_SCRIPT = """
import hashlib, json, sys
sys.path.insert(0, sys.argv[1])
from repro import pipeline
from repro.core import array_program as AP

g = AP.layernorm_matmul_program(32.0)
dims = {"M": 2, "K": 4, "N": 2}
kern = pipeline.compile(g, dims, backend="py")
cache = pipeline.default_cache()
key = pipeline.CacheKey.make(
    g.fingerprint(), "py", dims, None, True,
    pipeline.CompileOptions(backend="py").cache_opts(
        stabilized=False, autotuned=False))
plan_path = cache.root / (key.digest() + ".json")
print(json.dumps({
    "cost": kern.cost,
    "snapshot": kern.snapshot_index,
    "sha": hashlib.sha256(plan_path.read_bytes()).hexdigest(),
}))
"""


def test_cross_process_contention_same_key(tmp_path):
    """Two subprocesses compile the same (fingerprint, dims, options)
    key concurrently: both succeed, the surviving on-disk plan is
    byte-identical from both sides, and nothing is quarantined or left
    half-written."""
    env = dict(os.environ, REPRO_KERNEL_CACHE=str(tmp_path))
    env.pop("REPRO_FAULT_PLAN", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", _CONTENTION_SCRIPT, str(SRC)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True) for _ in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err
        outs.append(json.loads(out.strip().splitlines()[-1]))
    assert outs[0] == outs[1]  # same plan, byte-identical envelope

    # zero corruption, zero leftovers, exactly one entry
    assert not list(tmp_path.glob("*.tmp"))
    assert not (tmp_path / "quarantine").exists()
    assert len(list(tmp_path.glob("*.json"))) == 1
    kc = C.KernelCache(root=tmp_path)
    assert (kc.stats.recovered_tmp, kc.stats.stale_locks) == (0, 0)
    # and the surviving entry reads back clean in this process
    from repro.core import array_program as AP
    g = AP.layernorm_matmul_program(32.0)
    kern = pipeline.compile(g, {"M": 2, "K": 4, "N": 2}, backend="py",
                            cache=kc)
    assert kern.cache_hit == "disk"
    assert kc.stats.corrupt_plans == 0 and kc.stats.quarantined == 0
