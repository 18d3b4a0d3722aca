"""JAX persistent compilation-cache placement — one rule, one setter.

Every entry point (the fleet router, the fabric scripts,
``tests/conftest.py``) places its cache through
:func:`enable_scoped_compilation_cache`, and the rule lives here only:

- ``JAX_COMPILATION_CACHE_DIR`` **set**: that directory is the cache.  jax
  reads the variable itself; this module sets no other path in code, so
  whoever runs the program decides where compiled programs persist.
- **unset**: one fixed, git-ignored directory inside the checkout,
  ``<repo>/.jax_cache`` — never a temporary name, a pid or a time: the
  directory is part of the cache key, so a path that moves never hits.
  Below it the cache is keyed by toolchain (``jax``/Python version — an
  upgraded toolchain never reads a stale cache), by a **tag** per harness
  (``tests``, ``fleet`` ...) and, for concurrent runs, by a
  scope: ``ACCELERATE_JAX_CACHE_SCOPE``, the pytest-xdist worker id, and
  the launched process id — concurrent jax processes never share a leaf.

**Prewarm distribution**: :func:`export_prewarm` packs the directory in
force into one toolchain-keyed archive, and :func:`load_prewarm` unpacks it
on a deploy host BEFORE the preflight/warmup — so production startup pays
zero cold compiles even on a fresh machine.  Loads are **version-keyed**: a
pack from a different jax/Python build is refused (its entries could never
hit), and every stale-version directory under the in-checkout root is swept
on load, so upgraded toolchains never accumulate dead weight.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tarfile
from pathlib import Path

PREWARM_MANIFEST = "prewarm_manifest.json"
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# the fixed in-checkout root used when CACHE_DIR_ENV is unset (.gitignore'd)
CACHE_ROOT = Path(__file__).resolve().parents[2] / ".jax_cache"


def toolchain_version_key() -> str:
    """The cache-keying toolchain tag: jax + Python version.  An entry
    compiled by one toolchain is dead weight (at best) to another."""
    import jax

    return f"jax{jax.__version__}-py{sys.version_info.major}.{sys.version_info.minor}"


def _process_scope() -> str:
    """The multi-process scope component: launched workers never share a
    cache directory (concurrent jax processes corrupt a shared cache — the
    documented flake the scoped dirs retired, which a 2-process
    ``accelerate_tpu launch`` would otherwise reintroduce).

    Keyed by the launcher's ``ACCELERATE_PROCESS_ID`` env when present —
    reading ``jax.process_index()`` here would *initialize* the backend and
    make the worker's later ``jax.distributed.initialize`` impossible, so
    jax is only consulted when the distributed runtime is already up
    (state.py has initialized it)."""
    pid = os.environ.get("ACCELERATE_PROCESS_ID")
    if pid is not None:
        return f"proc{pid}"
    from ..state import _jax_distributed_initialized

    if _jax_distributed_initialized:
        import jax

        if jax.process_count() > 1:
            return f"proc{jax.process_index()}"
    return ""


def scoped_cache_dir(tag: str = "run") -> str:
    """The cache directory in force — created if missing.
    ``JAX_COMPILATION_CACHE_DIR`` verbatim when set; otherwise the
    (toolchain, tag, scope, process) leaf under the in-checkout root."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        path = Path(env)
    else:
        scope = os.environ.get("ACCELERATE_JAX_CACHE_SCOPE") or os.environ.get(
            "PYTEST_XDIST_WORKER", ""
        )
        leaf = "-".join(part for part in (tag, scope, _process_scope()) if part)
        path = CACHE_ROOT / toolchain_version_key() / leaf
    path.mkdir(parents=True, exist_ok=True)
    return str(path)


def export_prewarm(dest: str, tag: str = "run") -> str:
    """Pack the compilation cache in force into one distributable archive.

    The archive carries a manifest keyed by :func:`toolchain_version_key`
    and ``tag``; ship it to deploy hosts and :func:`load_prewarm` it before
    ``preflight``/``warmup`` — the whole bucket ladder then compiles from
    cache hits.  Returns the archive path."""
    src = Path(scoped_cache_dir(tag))
    dest_path = Path(dest)
    dest_path.parent.mkdir(parents=True, exist_ok=True)
    entries = sorted(p.name for p in src.iterdir() if p.is_file())
    manifest = {
        "version_key": toolchain_version_key(),
        "tag": tag,
        "entries": entries,
    }
    manifest_file = src / PREWARM_MANIFEST
    manifest_file.write_text(json.dumps(manifest, indent=1))
    try:
        with tarfile.open(dest_path, "w") as tar:
            tar.add(manifest_file, arcname=PREWARM_MANIFEST)
            for name in entries:
                tar.add(src / name, arcname=f"cache/{name}")
    finally:
        manifest_file.unlink(missing_ok=True)
    return str(dest_path)


def sweep_stale_versions() -> list[str]:
    """Remove every subdirectory of the in-checkout cache root keyed by a
    DIFFERENT toolchain than the current one (the version-keyed eviction: an
    upgraded jax or Python never reads — or pays disk for — a stale cache).
    A ``JAX_COMPILATION_CACHE_DIR`` placed from outside has no toolchain
    leaves and is not this module's to sweep.  Returns the swept names."""
    current = toolchain_version_key()
    swept = []
    if os.environ.get(CACHE_DIR_ENV) or not CACHE_ROOT.is_dir():
        return swept
    for child in sorted(CACHE_ROOT.iterdir()):
        if child.is_dir() and child.name != current:
            shutil.rmtree(child, ignore_errors=True)
            swept.append(child.name)
    return swept


def load_prewarm(archive: str, tag: str = "run") -> dict:
    """Unpack a prewarm archive into the cache directory in force.

    Version-keyed: an archive built by a different toolchain is REFUSED
    (``{"loaded": 0, "stale": True}`` — its entries could never hit and a
    deserialized foreign executable is exactly the corruption class the
    scoped dirs retired).  Either way, stale-version directories under the
    in-checkout root are swept.  Never raises on a bad archive — a broken
    prewarm pack degrades to a cold start, not a failed deploy."""
    report = {"loaded": 0, "stale": False, "swept": [], "version_key": toolchain_version_key()}
    try:
        with tarfile.open(archive, "r") as tar:
            try:
                member = tar.extractfile(PREWARM_MANIFEST)
            except KeyError:  # no manifest member at all (foreign tar)
                member = None
            manifest = json.loads(member.read().decode()) if member else {}
            if manifest.get("version_key") != toolchain_version_key():
                report["stale"] = True
            else:
                dest = Path(scoped_cache_dir(tag))
                for m in tar.getmembers():
                    name = m.name
                    if not (m.isfile() and name.startswith("cache/")):
                        continue
                    leaf = Path(name).name  # flatten: no traversal, ever
                    src = tar.extractfile(m)
                    if src is None:  # pragma: no cover - malformed member
                        continue
                    (dest / leaf).write_bytes(src.read())
                    report["loaded"] += 1
    except (OSError, tarfile.TarError, json.JSONDecodeError) as e:
        report["stale"] = True
        report["error"] = str(e)
    report["swept"] = sweep_stale_versions()
    return report


def enable_scoped_compilation_cache(
    tag: str = "run",
    *,
    min_compile_time_secs: float = 0.5,
    min_entry_size_bytes: int = 0,
) -> str:
    """Turn jax's persistent compilation cache on for the directory in
    force and return it.  The one place the path is set in code — and only
    when ``JAX_COMPILATION_CACHE_DIR`` is unset (jax reads the variable
    itself; a placement made from outside is never overridden)."""
    import jax

    d = scoped_cache_dir(tag)
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_compile_time_secs)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", min_entry_size_bytes)
    return d
