"""Compilation-cache placement (utils/compile_cache.py), one rule:
``JAX_COMPILATION_CACHE_DIR`` set -> that directory, and no path is set in
code; unset -> the fixed, git-ignored ``<repo>/.jax_cache`` with toolchain /
tag / scope / process leaves below it.  Plus the prewarm pack distribution
and the version-keyed eviction, against whichever directory is in force."""

import io
import json
import tarfile
from pathlib import Path

import jax
import pytest

from accelerate_tpu.utils import compile_cache
from accelerate_tpu.utils.compile_cache import (
    CACHE_DIR_ENV,
    PREWARM_MANIFEST,
    enable_scoped_compilation_cache,
    export_prewarm,
    load_prewarm,
    scoped_cache_dir,
    sweep_stale_versions,
    toolchain_version_key,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def unset_root(tmp_path, monkeypatch):
    """Variable unset, no scope; the in-checkout root redirected to a tmp
    dir so the tests never write into the real ``.jax_cache``."""
    for name in (CACHE_DIR_ENV, "ACCELERATE_JAX_CACHE_SCOPE", "PYTEST_XDIST_WORKER",
                 "ACCELERATE_PROCESS_ID"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(compile_cache, "CACHE_ROOT", tmp_path / "root")
    return tmp_path / "root"


@pytest.fixture
def config_updates(monkeypatch):
    """Record every ``jax.config.update`` the helper makes (and apply none)."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    return calls


def test_variable_set_is_the_directory_and_no_path_is_set_in_code(
        tmp_path, monkeypatch, config_updates):
    placed = tmp_path / "placed-from-outside"
    monkeypatch.setenv(CACHE_DIR_ENV, str(placed))
    monkeypatch.setenv("ACCELERATE_PROCESS_ID", "3")  # leaves apply only when unset
    for tag in ("tests", "bench", "smoke"):
        assert scoped_cache_dir(tag) == str(placed)
    assert enable_scoped_compilation_cache("fleet") == str(placed)
    assert placed.is_dir()
    assert "jax_compilation_cache_dir" not in [name for name, _ in config_updates]
    assert sweep_stale_versions() == []  # a directory placed from outside is not swept


def test_variable_unset_is_one_fixed_path_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    assert compile_cache.CACHE_ROOT == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    source = Path(compile_cache.__file__).read_text()
    for moving in ("tempfile", "getpid", "time.time", "/tmp"):
        assert moving not in source  # never a temporary name, a pid or a time


def test_unset_dir_keys_on_toolchain_and_tag_and_is_stable(unset_root, config_updates):
    d_tests, d_bench = scoped_cache_dir("tests"), scoped_cache_dir("bench")
    assert d_tests != d_bench and d_tests == scoped_cache_dir("tests")
    assert Path(d_tests) == unset_root / toolchain_version_key() / "tests"
    assert Path(d_tests).is_dir() and Path(d_bench).is_dir()
    assert enable_scoped_compilation_cache("tests") == d_tests
    assert ("jax_compilation_cache_dir", d_tests) in config_updates  # the one setter


def test_scope_env_isolates_concurrent_runs(unset_root, monkeypatch):
    base = scoped_cache_dir("tests")
    monkeypatch.setenv("ACCELERATE_JAX_CACHE_SCOPE", "runA")
    a = scoped_cache_dir("tests")
    monkeypatch.setenv("ACCELERATE_JAX_CACHE_SCOPE", "runB")
    b = scoped_cache_dir("tests")
    assert len({base, a, b}) == 3
    # the pytest-xdist worker id scopes automatically
    monkeypatch.delenv("ACCELERATE_JAX_CACHE_SCOPE")
    monkeypatch.setenv("PYTEST_XDIST_WORKER", "gw3")
    assert scoped_cache_dir("tests").endswith("tests-gw3")


def test_scoped_cache_dir_per_launched_process(unset_root, monkeypatch):
    """Concurrent launched processes never share a leaf: the scope is keyed
    by the launcher's ACCELERATE_PROCESS_ID (reading jax.process_index()
    would initialize the backend before jax.distributed.initialize)."""
    monkeypatch.setenv("ACCELERATE_PROCESS_ID", "0")
    d0 = scoped_cache_dir("tests")
    monkeypatch.setenv("ACCELERATE_PROCESS_ID", "1")
    d1 = scoped_cache_dir("tests")
    assert d0.endswith("tests-proc0") and d1.endswith("tests-proc1")
    monkeypatch.delenv("ACCELERATE_PROCESS_ID")
    assert scoped_cache_dir("tests").endswith("/tests")
    monkeypatch.setenv("ACCELERATE_JAX_CACHE_SCOPE", "w3")
    monkeypatch.setenv("ACCELERATE_PROCESS_ID", "2")
    assert scoped_cache_dir("tests").endswith("tests-w3-proc2")


# ---------------------------------------------------------------------------
# prewarm pack + version-keyed eviction
# ---------------------------------------------------------------------------


def _fake_warm_cache(tag, entries):
    d = Path(scoped_cache_dir(tag))
    for name, payload in entries.items():
        (d / name).write_bytes(payload)
    return d


@pytest.mark.parametrize("placed_from_outside", [False, True], ids=["unset", "variable_set"])
def test_prewarm_export_load_roundtrip(unset_root, tmp_path, monkeypatch, placed_from_outside):
    """A warmed cache packs into one toolchain-keyed archive; loading it
    into a fresh directory reproduces every entry byte-for-byte — against
    whichever directory is in force."""
    def place(name):
        if placed_from_outside:
            monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path / name))
        else:
            monkeypatch.setattr(compile_cache, "CACHE_ROOT", tmp_path / name)

    entries = {"prog_a.bin": b"\x01\x02xla", "prog_b.bin": b"\x03serving"}
    place("src")
    _fake_warm_cache("deploy", entries)
    pack = export_prewarm(str(tmp_path / "prewarm.tar"), "deploy")
    with tarfile.open(pack) as tar:
        manifest = json.loads(tar.extractfile(PREWARM_MANIFEST).read())
    assert manifest["version_key"] == toolchain_version_key()
    assert manifest["entries"] == sorted(entries)

    place("dst")
    report = load_prewarm(pack, "deploy")
    assert report["loaded"] == 2 and not report["stale"]
    dst = Path(scoped_cache_dir("deploy"))
    assert str(dst).startswith(str(tmp_path / "dst"))
    for name, payload in entries.items():
        assert (dst / name).read_bytes() == payload


def test_prewarm_refuses_foreign_toolchain(unset_root, tmp_path):
    """A pack built by a different jax/Python build is refused — loaded=0,
    stale=True, nothing extracted; a broken archive degrades the same way
    instead of failing the deploy."""
    _fake_warm_cache("deploy", {"prog.bin": b"x"})
    pack = export_prewarm(str(tmp_path / "p.tar"), "deploy")
    foreign = str(tmp_path / "foreign.tar")
    with tarfile.open(pack) as tar, tarfile.open(foreign, "w") as out:
        for m in tar.getmembers():
            data = tar.extractfile(m).read()
            if m.name == PREWARM_MANIFEST:
                data = json.dumps({"version_key": "jax0.0.1-py2.7",
                                   "tag": "deploy", "entries": ["prog.bin"]}).encode()
            m.size = len(data)
            out.addfile(m, io.BytesIO(data))
    report = load_prewarm(foreign, "other")
    assert report["stale"] and report["loaded"] == 0
    assert not (Path(scoped_cache_dir("other")) / "prog.bin").exists()
    bad = tmp_path / "bad.tar"
    bad.write_bytes(b"not a tar")
    rep2 = load_prewarm(str(bad), "other")
    assert rep2["stale"] and rep2["loaded"] == 0
    noman = tmp_path / "nomanifest.tar"  # a valid tar with NO manifest member
    with tarfile.open(noman, "w") as out:
        info = tarfile.TarInfo("cache/prog.bin")
        info.size = 1
        out.addfile(info, io.BytesIO(b"x"))
    rep3 = load_prewarm(str(noman), "other")
    assert rep3["stale"] and rep3["loaded"] == 0


def test_load_prewarm_sweeps_stale_version_dirs(unset_root, tmp_path):
    """Version-keyed eviction: loading (or sweeping directly) removes every
    subdir of the in-checkout root keyed by a different toolchain, and ONLY
    those."""
    _fake_warm_cache("deploy", {"prog.bin": b"x"})
    stale = unset_root / "jax0.3.0-py3.8" / "deploy"
    stale.mkdir(parents=True)
    (stale / "dead.bin").write_bytes(b"stale")
    pack = export_prewarm(str(tmp_path / "p.tar"), "deploy")
    report = load_prewarm(pack, "deploy")
    assert report["swept"] == ["jax0.3.0-py3.8"]
    assert not stale.exists()
    assert (unset_root / toolchain_version_key()).is_dir()  # current survives
    assert sweep_stale_versions() == []                     # idempotent
