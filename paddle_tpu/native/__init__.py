"""Native (C++) runtime components, built on demand with the system
toolchain and loaded via ctypes (no pybind11 in this environment).

Reference analog: Paddle ships its control plane (TCPStore, watchdog, data
feeders) as C++ inside libpaddle; here each component is a small shared
library compiled at first use and cached next to the source (keyed by a
source hash, so edits rebuild automatically)."""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")
_lock = threading.Lock()
_libs = {}


def build_sources(name: str, sources, extra_flags=(),
                  build_dir=None) -> ctypes.CDLL:
    """Compile arbitrary C++ sources to a cached .so and dlopen it
    (shared by the built-in components and user cpp_extension ops)."""
    with _lock:
        h = hashlib.sha256()
        for src in sources:
            with open(src, "rb") as f:
                h.update(f.read())
        h.update(" ".join(extra_flags).encode())
        tag = h.hexdigest()[:16]
        key = (name, tag, build_dir)
        if key in _libs:
            return _libs[key]
        out_dir = build_dir or _BUILD
        os.makedirs(out_dir, exist_ok=True)
        so = os.path.join(out_dir, f"lib{name}-{tag}.so")
        if not os.path.exists(so):
            tmp = so + f".tmp{os.getpid()}"
            cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                   "-pthread", "-o", tmp, *sources, *extra_flags]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True)
            except FileNotFoundError as e:
                # _build/ is git-ignored: a fresh checkout builds here
                raise RuntimeError(
                    f"native build of {name} needs g++ on PATH, and it "
                    f"is missing ({e})") from e
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"native build of {name} failed:\n{e.stderr}") from e
            os.replace(tmp, so)  # atomic vs concurrent builders
        lib = ctypes.CDLL(so)
        _libs[key] = lib
        return lib


def build_and_load(name: str, extra_flags=()) -> ctypes.CDLL:
    """Compile native/<name>.cc to a cached .so and dlopen it."""
    return build_sources(name, [os.path.join(_DIR, name + ".cc")],
                         extra_flags)
