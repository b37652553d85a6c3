"""On-demand g++ build of the native library.

Replaces the reference's cmake-driven native deps (CMakeLists.txt,
scripts/build.sh) with a zero-config build: first use compiles
``csrc/*.cc`` into ``build/libedl_native.so``; failures degrade to the
pure-Python fallbacks rather than breaking the import.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from edl_tpu.utils.logger import get_logger

logger = get_logger(__name__)

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_ROOT, "csrc")
_OUT = os.path.join(_ROOT, "build", "libedl_native.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_failed = False


# standalone binaries (own main()), not part of the shared library
_STANDALONE = {"coordd.cc"}

# sources with extra link deps, dropped (with their flags) when the dep
# is missing on the host — the library still builds without them and
# the Python wrappers fall back (imagedec -> cv2 path)
_OPTIONAL = {"imagedec.cc": ["-ljpeg"]}


def _sources() -> list[str]:
    if not os.path.isdir(_SRC_DIR):
        return []
    return sorted(os.path.join(_SRC_DIR, f) for f in os.listdir(_SRC_DIR)
                  if f.endswith(".cc") and f not in _STANDALONE)


def _digest(sources: list[str], flags: list[str]) -> str:
    """Content key of one build: the bytes of every source plus the
    flags.  Not mtimes — a copied or archived tree does not keep them,
    and a binary built from other sources must never be loaded."""
    h = hashlib.sha256("\0".join(flags).encode())
    for src in sources:
        with open(src, "rb") as f:
            h.update(b"\0" + os.path.basename(src).encode() + b"\0")
            h.update(f.read())
    return h.hexdigest()


def _stale(out: str, digest: str) -> bool:
    """True unless ``out`` exists beside a digest file naming exactly
    the sources + flags about to be built."""
    try:
        with open(out + ".digest") as f:
            return f.read().strip() != digest or not os.path.exists(out)
    except OSError:
        return True


def ensure_built() -> ctypes.CDLL | None:
    """Compile (if stale) and dlopen the native library; None if the
    toolchain or sources are unavailable."""
    global _lib, _failed
    # edl-lint: disable=blocking-under-lock — once-only build gate:
    # serializing the compile subprocess is this lock's whole purpose
    with _lock:
        if _lib is not None or _failed:
            return _lib
        sources = _sources()
        if not sources:
            _failed = True
            return None
        try:
            extra = sorted({f for s in sources
                            for f in _OPTIONAL.get(os.path.basename(s), [])})
            flags = ["-O3", "-shared", "-fPIC"]
            digest = _digest(sources, flags + extra)
            if _stale(_OUT, digest):
                try:
                    _compile([*flags, *sources, *extra], _OUT, digest)
                except subprocess.CalledProcessError as e:
                    # retry without the optional sources (missing dep,
                    # e.g. no libjpeg): the core library must still build
                    core = [s for s in sources
                            if os.path.basename(s) not in _OPTIONAL]
                    if core == sources:
                        raise
                    logger.warning(
                        "optional native sources dropped (%s); %s",
                        ", ".join(sorted(_OPTIONAL)),
                        (getattr(e, "stderr", "") or str(e)).strip()[:300])
                    _compile([*flags, *core], _OUT, digest)
            _lib = ctypes.CDLL(_OUT)
        except (subprocess.CalledProcessError, OSError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            logger.warning("native build unavailable (%s); using Python "
                           "fallbacks", detail.strip()[:500])
            _failed = True
        return _lib


def _compile(flags: list[str], out: str, digest: str) -> None:
    """g++ to a process-unique tmp then atomic rename: concurrent
    builders (launcher subprocesses) must never tear the output.  The
    digest file lands AFTER the binary, so a build killed in between
    reads as stale."""
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = ["g++", "-std=c++17", "-pthread", *flags, "-o", tmp]
    logger.info("building native: %s", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
        with open(tmp, "w") as f:
            f.write(digest + "\n")
        os.replace(tmp, out + ".digest")
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def native_available() -> bool:
    return ensure_built() is not None


def ensure_coordd() -> str | None:
    """Compile (if stale) the native coordination daemon
    (csrc/coordd.cc); returns the binary path or None if the toolchain
    is unavailable."""
    src = os.path.join(_SRC_DIR, "coordd.cc")
    out = os.path.join(_ROOT, "build", "coordd")
    if not os.path.exists(src):
        return None
    # edl-lint: disable=blocking-under-lock — same build gate: one
    # compile at a time is the point
    with _lock:
        try:
            digest = _digest([src], ["-O2"])
            if _stale(out, digest):
                _compile(["-O2", src], out, digest)
        except (subprocess.CalledProcessError, OSError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            logger.warning("coordd build failed: %s", detail.strip()[:500])
            return None
    return out
