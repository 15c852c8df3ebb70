"""Native (C++) runtime components, built on demand with g++.

The reference keeps its PS transport, server, and embedding cache in C++
(ps-lite, src/hetu_cache — SURVEY.md §2.2/2.3).  Here the host-side systems
code that survives on TPU is likewise native: this package compiles small
C++ shared libraries on first use and loads them via ctypes.

What is loaded is decided by the committed sources alone: a library is
built into ``_build/<hash>/`` where the hash covers the source, its
headers and the compiler flags, so a stale or foreign ``.so`` lying in
the tree (file times do not survive a copy) can never be picked up.  A
build that fails raises — nothing drops to a slower path in silence.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD = os.path.join(_DIR, "_build")

# lib_name -> path of every library this process has loaded
loaded = {}


def build_and_load(src_name, lib_name, extra_flags=(), deps=()):
    """Compile ``src_name`` (+ header ``deps``) to
    ``_build/<hash>/<lib_name>`` unless that exact build exists, and
    dlopen it.  Raises RuntimeError when the compiler is missing or
    fails."""
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", *extra_flags]
    h = hashlib.sha256(" ".join(cmd).encode())
    for name in (src_name, *deps):
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(b"\0" + name.encode() + b"\0" + f.read())
    out_dir = os.path.join(_BUILD, h.hexdigest()[:16])
    lib = os.path.join(out_dir, lib_name)
    if not os.path.exists(lib):
        os.makedirs(out_dir, exist_ok=True)
        # build under a private name, then rename: concurrent processes
        # (test workers, PS server + workers) never load a half-written file
        tmp = f"{lib}.{os.getpid()}.tmp"
        try:
            subprocess.run([*cmd, os.path.join(_DIR, src_name), "-o", tmp],
                           check=True, capture_output=True, text=True)
        except FileNotFoundError as e:
            raise RuntimeError(
                f"native library {lib_name} needs g++ to build "
                f"{src_name}: {e}") from e
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"g++ failed building {src_name} -> {lib_name}:\n"
                f"{e.stderr[-2000:]}") from e
        os.replace(tmp, lib)
    loaded[lib_name] = lib
    return ctypes.CDLL(lib)
