"""The one build rule of the native kernels.

A library is compiled on first use from the .cc files git holds, into
`native/build/` (git-ignored) under a name that carries a hash of the
source bytes, the compiler flags and — for `-march=native` builds — the
host CPU's feature flags.  So a source edit rebuilds, and a binary that
was copied in from a machine with another CPU is never loaded: its name
does not match here, whatever its mtime says.
"""

from __future__ import annotations

import functools
import hashlib
import os
import platform
import subprocess
import threading

BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "build")


class BuildError(RuntimeError):
    """The toolchain could not produce the library (no g++, or it
    refused the source).  The only native failure a caller may answer
    with a portable path; a library that built and then fails to load
    is an error."""


@functools.cache
def _cpu_flags() -> bytes:
    """What `-march=native` resolves against on this host."""
    try:
        with open("/proc/cpuinfo", encoding="ascii", errors="replace") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip().encode()
    except OSError:
        pass
    return platform.processor().encode()


def build(name: str, src: str, deps: tuple[str, ...] = (),
          march_native: bool = True) -> str:
    """Path of lib<name> built from `src` (which #includes `deps`) for
    this host, compiling it if that exact build is not there yet."""
    flags = ["-O3", "-shared", "-fPIC"]
    h = hashlib.sha256()
    for path in (src, *deps):
        with open(path, "rb") as f:
            h.update(f.read())
    if march_native:
        flags.insert(1, "-march=native")
        h.update(platform.machine().encode() + _cpu_flags())
    h.update(" ".join(flags).encode())
    so = os.path.join(BUILD_DIR, f"lib{name}.{h.hexdigest()[:16]}.so")
    if os.path.exists(so):
        return so
    os.makedirs(BUILD_DIR, exist_ok=True)
    # Private temp + os.replace: a concurrent booter never CDLLs a
    # half-written .so.  Private to the thread too: the first requests
    # of a fresh checkout load a kernel from several threads at once,
    # and two that shared a temp moved or unlinked each other's.
    tmp = f"{so}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        subprocess.run(["g++", *flags, "-o", tmp, src],
                       check=True, capture_output=True, text=True)
        os.replace(tmp, so)
    except subprocess.CalledProcessError as e:
        raise BuildError(f"g++ failed on {src}: {e.stderr[-2000:]}") from e
    except OSError as e:
        raise BuildError(f"cannot build {src}: {e}") from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return so
