"""Native (C++) data-plane kernels, bound via ctypes.

``partitioner.cc`` is compiled on first use (g++ is part of the baked
toolchain) into ``build/libabt_native-<key>.so``, where the key hashes
the source, the compiler flags and this host's CPU feature flags: a
binary is only ever loaded on the kind of machine that built it from
this exact source (``-march=native`` output copied from another host is
a SIGILL).  If compilation is impossible the pure-Python fallbacks take
over — with a WARNING, and :func:`status` says so.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np
import pyarrow as pa

log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "partitioner.cc")
_BUILD_DIR = os.path.join(_HERE, "build")
_CXXFLAGS = ["-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]

_lib: Optional[ctypes.CDLL] = None
_lib_lock = threading.Lock()
_build_failed = False


def _cpu_flags() -> bytes:
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    return line
    except OSError:
        pass
    return b""


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(_cpu_flags())
    return os.path.join(_BUILD_DIR, f"libabt_native-{h.hexdigest()[:16]}.so")


def _compile(so: str) -> bool:
    """Build to a private temp name, then rename: executor and task-runner
    children may build at once, and none may load a half-written file."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_CXXFLAGS, "-o", tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        log.warning(
            "native partitioner build failed; shuffle partitioning runs "
            "the pure-Python path: %s %s",
            e,
            (getattr(e, "stderr", b"") or b"").decode(errors="replace")[-2000:],
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def status() -> str:
    """``"loaded"`` or ``"python-fallback"`` (builds/loads on first call)."""
    return "loaded" if get_lib() is not None else "python-fallback"


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so) and not _compile(so):
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("native partitioner %s did not load: %s", so, e)
            _build_failed = True
            return None
        u8p = ctypes.c_void_p
        lib.abt_hash_int.argtypes = [
            u8p,
            ctypes.c_int32,
            ctypes.c_int32,
            u8p,
            ctypes.c_int64,
            u8p,
        ]
        lib.abt_hash_f64.argtypes = [u8p, u8p, ctypes.c_int64, u8p]
        lib.abt_hash_f32.argtypes = [u8p, u8p, ctypes.c_int64, u8p]
        lib.abt_hash_bool.argtypes = [u8p, u8p, ctypes.c_int64, u8p]
        lib.abt_hash_str32.argtypes = [u8p, u8p, u8p, ctypes.c_int64, u8p]
        lib.abt_hash_str64.argtypes = [u8p, u8p, u8p, ctypes.c_int64, u8p]
        lib.abt_finish_mod.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64, u8p]
        _lib = lib
        return _lib


# arrow type -> (byte width, is_signed); mirrors the python fallback's
# astype(int64) sign/zero extension semantics
_INT_SPECS = {
    pa.int8(): (1, 1),
    pa.int16(): (2, 1),
    pa.int32(): (4, 1),
    pa.int64(): (8, 1),
    pa.uint8(): (1, 0),
    pa.uint16(): (2, 0),
    pa.uint32(): (4, 0),
    pa.date32(): (4, 1),
    pa.date64(): (8, 1),
}


def _np_ptr(a: np.ndarray) -> ctypes.c_void_p:
    return ctypes.c_void_p(a.ctypes.data)


def native_hash_partition_indices(
    batch: pa.RecordBatch, exprs, n: int
) -> Optional[np.ndarray]:
    """Partition ids via the C++ kernel; None → caller falls back to Python.

    Bit-identical to exec.operators.hash_partition_indices by construction
    (see partitioner.cc header).
    """
    lib = get_lib()
    if lib is None:
        return None

    n_rows = batch.num_rows
    h = np.zeros(n_rows, dtype=np.uint64)
    hp = _np_ptr(h)

    cols = []
    for e in exprs:
        v = e.evaluate(batch)
        if isinstance(v, pa.ChunkedArray):
            v = v.combine_chunks()
        if isinstance(v, pa.Scalar):
            return None  # constant keys: let the python path handle it
        if v.offset != 0:
            v = pa.concat_arrays([v])  # re-materialize at offset 0
            if v.offset != 0:
                return None
        cols.append(v)

    for v in cols:
        t = v.type
        bufs = v.buffers()
        validity = bufs[0].address if bufs[0] is not None and v.null_count else None
        vp = ctypes.c_void_p(validity) if validity else None
        if pa.types.is_string(t):
            lib.abt_hash_str32(
                ctypes.c_void_p(bufs[1].address),
                ctypes.c_void_p(bufs[2].address),
                vp,
                n_rows,
                hp,
            )
        elif pa.types.is_large_string(t):
            lib.abt_hash_str64(
                ctypes.c_void_p(bufs[1].address),
                ctypes.c_void_p(bufs[2].address),
                vp,
                n_rows,
                hp,
            )
        elif pa.types.is_boolean(t):
            lib.abt_hash_bool(ctypes.c_void_p(bufs[1].address), vp, n_rows, hp)
        elif pa.types.is_float64(t):
            lib.abt_hash_f64(ctypes.c_void_p(bufs[1].address), vp, n_rows, hp)
        elif pa.types.is_float32(t):
            lib.abt_hash_f32(ctypes.c_void_p(bufs[1].address), vp, n_rows, hp)
        elif pa.types.is_timestamp(t):
            lib.abt_hash_int(ctypes.c_void_p(bufs[1].address), 8, 1, vp, n_rows, hp)
        elif t in _INT_SPECS:
            size, signed = _INT_SPECS[t]
            lib.abt_hash_int(
                ctypes.c_void_p(bufs[1].address), size, signed, vp, n_rows, hp
            )
        else:
            return None  # unsupported key type → python fallback

    out = np.empty(n_rows, dtype=np.int64)
    lib.abt_finish_mod(hp, n_rows, n, _np_ptr(out))
    return out
