"""On-demand build of the native extensions.

The driver's environment runs bench.py and pytest with no manual build
step, so the C engines must build themselves whenever a C compiler is
present.  A build is a ~100ms ``cc -O2 -shared``.  A built library is
keyed on a hash of its source bytes and compile flags, carried in its
FILE NAME (``_replay.<12 hex>.so``): only what git would commit decides
which library runs — a tree copied without useful mtimes, or one that
brought an untracked ``.so`` along, can never load a library built from
other source.  Writes are atomic (compile to a temp name, then
``os.replace``) so concurrent builders — parallel pytest workers, a
bench racing a test run — never load a half-written library.

``ensure_replay()`` is called from models/replay.py at first load and
from tests/conftest.py; a missing compiler degrades loudly (one warning
on stderr) to the pure-Python spec replay rather than silently running
~10x slower (the round-2 failure mode: the number of record did not
contain the work).  chip_smoke.py refuses to run degraded.
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import os
import shutil
import subprocess
import sys
import sysconfig
import tempfile

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_WARNED: set[str] = set()


def _warn_once(key: str, msg: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        print(f"kubernetes_tpu/native: {msg}", file=sys.stderr)


def _compiler() -> str | None:
    for cand in (os.environ.get("CC"), "cc", "gcc", "clang"):
        if cand and shutil.which(cand):
            return cand
    return None


_BASE_FLAGS = ["-O2", "-fPIC", "-Wall", "-shared"]


def _keyed_name(src_path: str, stem: str, suffix: str,
                flags: list[str]) -> str:
    h = hashlib.sha256()
    with open(src_path, "rb") as f:
        h.update(f.read())
    h.update(b"\0" + " ".join(flags).encode())
    return f"{stem}.{h.hexdigest()[:12]}{suffix}"


def _build(src: str, stem: str, suffix: str,
           extra_flags: list[str]) -> str | None:
    """Compile src -> {stem}.<hash of source+flags>{suffix} unless that
    exact file exists. Returns its path, or None when it cannot be
    built; a library of any other hash is never handed back."""
    src_path = os.path.join(_NATIVE_DIR, src)
    flags = _BASE_FLAGS + extra_flags
    out = _keyed_name(src_path, stem, suffix, flags)
    out_path = os.path.join(_NATIVE_DIR, out)
    if os.path.exists(out_path):
        return out_path
    cc = _compiler()
    if cc is None:
        _warn_once(
            f"no-cc-{stem}",
            f"no C compiler found; {out} not built — degrading to the "
            "pure-Python path. Install cc/gcc/clang.",
        )
        return None
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_NATIVE_DIR)
    os.close(fd)
    cmd = [cc, *flags, "-o", tmp, src_path]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
        if proc.returncode != 0:
            _warn_once(
                f"fail-{src}",
                f"building {out} failed ({' '.join(cmd)}):\n{proc.stderr}",
            )
            os.unlink(tmp)
            return None
        os.replace(tmp, out_path)  # atomic: loaders see nothing or all
    except Exception as exc:  # timeout, OSError — degrade, don't crash
        _warn_once(f"exc-{src}", f"building {out} raised {exc!r}")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    # builds of other source/flags are dead weight (never loaded)
    for old in glob.glob(os.path.join(_NATIVE_DIR, f"{stem}.*{suffix}")):
        if old != out_path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return out_path


def ensure_replay() -> str | None:
    """Build (if absent) and return the path to the replay library."""
    return _build("replay.c", "_replay", ".so", [])


def _ensure_ext(stem: str) -> str | None:
    """Build a CPython extension from {stem}.c (needs Python headers)."""
    inc = sysconfig.get_paths().get("include")
    if not inc or not os.path.exists(os.path.join(inc, "Python.h")):
        return None
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return _build(f"{stem}.c", stem, suffix, [f"-I{inc}"])


def ensure_kquantity() -> str | None:
    return _ensure_ext("_kquantity")


def ensure_ktlv() -> str | None:
    return _ensure_ext("_ktlv")


def load_extension(stem: str):
    """Build (if absent) and import the CPython extension {stem} from
    its hash-keyed file, as kubernetes_tpu.native.{stem}; None when it
    cannot be built (callers degrade to their pure-Python path). The
    import system finds extensions by module name, which a keyed file
    name is not — so the file is loaded by path."""
    name = f"kubernetes_tpu.native.{stem}"
    mod = sys.modules.get(name)
    if mod is not None:
        return mod
    path = _ensure_ext(stem)
    if path is None:
        return None
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


def ensure_all() -> tuple:
    """-> (replay, kquantity, ktlv) library paths; None where an engine
    could not be built."""
    return ensure_replay(), ensure_kquantity(), ensure_ktlv()
