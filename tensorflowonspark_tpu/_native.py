"""Lazy g++ build of the ``native/*.cpp`` libraries, keyed by source hash.

shm.py and _tfrecord_native.py dlopen a shared object built on first
use. The binary's NAME carries a hash of the source and the compile
command (``_lib<stem>-<hash>.so``), so the question "is this binary the
one this source builds?" never rests on file times — which mean nothing
after a checkout or a copy of the tree — and a stale or foreign
``_lib<stem>*.so`` is simply never the name that gets loaded.
"""

import ctypes
import glob
import hashlib
import os
import subprocess

_PKG = os.path.dirname(os.path.abspath(__file__))
_NATIVE = os.path.join(os.path.dirname(_PKG), "native")
_FLAGS = ["-O2", "-std=c++17", "-shared", "-fPIC"]


def load(source, stem, link_flags=()):
    """dlopen the library built from ``native/<source>``, building it
    first unless the binary for exactly this source already exists.
    Raises (``OSError``/``CalledProcessError``) where it cannot be
    built; callers decide whether that is fatal."""
    src = os.path.join(_NATIVE, source)
    link_flags = list(link_flags)
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(
                f.read() + " ".join(_FLAGS + link_flags).encode())
    except FileNotFoundError:
        # installed without native/: whatever binary shipped is the one
        built = sorted(glob.glob(os.path.join(
            _PKG, "_lib{}-*.so".format(stem))))
        if not built:
            raise
        return ctypes.CDLL(built[-1])
    so = os.path.join(_PKG, "_lib{}-{}.so".format(
        stem, digest.hexdigest()[:16]))
    if not os.path.exists(so):
        # per-pid temp: concurrent executor processes all lazily build,
        # and a shared temp name would tear; os.replace of a complete
        # file is atomic
        tmp = "{}.{}.tmp".format(so, os.getpid())
        try:
            subprocess.run(["g++"] + _FLAGS + ["-o", tmp, src] + link_flags,
                           check=True, capture_output=True)
            os.replace(tmp, so)
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass
        for old in glob.glob(os.path.join(_PKG,
                                          "_lib{}*.so".format(stem))):
            if old != so:
                try:
                    os.unlink(old)
                except OSError:
                    pass
    return ctypes.CDLL(so)
