"""Shared loader for the C++ runtime libraries under native/.

One code path for building (`make <target>.so`) and ctypes-loading every
native extension, used by native_batcher.py and native_bpe.py. Build is
serialized across *processes* with an fcntl file lock — preprocess fans out
a multiprocessing Pool, and without the lock every fresh worker would race
`make` in the same directory and could dlopen a half-written library.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
import warnings
from typing import Callable, Dict, Optional

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_cache: Dict[str, Optional[ctypes.CDLL]] = {}
_cache_lock = threading.Lock()


def load_native_lib(
    so_name: str,
    configure: Callable[[ctypes.CDLL], None],
    *,
    auto_build: bool = True,
) -> Optional[ctypes.CDLL]:
    """Load native/<so_name>, (re)building it first if absent or stale.

    The libraries are build products, never committed: a binary built on
    another CPU can die with an illegal instruction that no `except` sees.
    `configure(lib)` sets restype/argtypes. Returns None when the library
    cannot be built or loaded — after a warning that says why, so a caller's
    pure-Python path is never taken silently. The result (including failure)
    is cached per process.
    """
    with _cache_lock:
        if so_name in _cache:
            return _cache[so_name]
        lib = _load(so_name, configure, auto_build)
        _cache[so_name] = lib
        return lib


def _needs_build(path: str) -> bool:
    """Missing, or older than its source (lib<name>.so <- <name>.cpp)."""
    if not os.path.exists(path):
        return True
    stem = os.path.basename(path).removeprefix("lib").removesuffix(".so")
    src = os.path.join(NATIVE_DIR, stem + ".cpp")
    return os.path.exists(src) and os.path.getmtime(src) > os.path.getmtime(path)


def _load(
    so_name: str, configure: Callable[[ctypes.CDLL], None], auto_build: bool
) -> Optional[ctypes.CDLL]:
    path = os.path.join(NATIVE_DIR, so_name)
    if auto_build and _needs_build(path):
        lock_path = os.path.join(NATIVE_DIR, ".build.lock")
        try:
            with open(lock_path, "w") as lock_file:
                fcntl.flock(lock_file, fcntl.LOCK_EX)
                try:
                    if _needs_build(path):  # a peer may have built it
                        subprocess.run(
                            ["make", "-s", so_name],
                            cwd=NATIVE_DIR,
                            check=True,
                            capture_output=True,
                            timeout=120,
                        )
                finally:
                    fcntl.flock(lock_file, fcntl.LOCK_UN)
        except subprocess.CalledProcessError as e:
            warnings.warn(
                f"building native/{so_name} failed (rc={e.returncode}): "
                f"{e.stderr.decode(errors='replace')[-500:]}"
            )
            return None
        except (subprocess.TimeoutExpired, OSError) as e:
            warnings.warn(f"building native/{so_name} failed: {e!r}")
            return None
    if not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        configure(lib)
    except (OSError, AttributeError) as e:
        warnings.warn(f"loading native/{so_name} failed: {e!r}")
        return None
    return lib
