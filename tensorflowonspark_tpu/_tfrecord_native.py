"""ctypes binding for the native TFRecord codec (native/tfrecord_codec.cpp).

Throughput path for TFRecord reads: one mmap/read of the file, one C
scan that validates framing + both CRCs and returns every record's
(offset, length), then zero-copy memoryview slices — instead of four
python-level reads and two python/c-extension crc calls per record.
Dense feature columns batch-decode straight into numpy arrays.

Follows the shm.py pattern: lazy g++ build cached next to the package
under a source-hash name (_native.py),
``available()`` False (and the pure-python tfrecord.py codec takes over)
wherever the toolchain is missing. tfrecord.py remains the canonical,
oracle-tested implementation; tests assert byte-exact agreement.
"""

import ctypes
import logging
import threading

import numpy as np

from tensorflowonspark_tpu import _native

logger = logging.getLogger(__name__)

_lib = None
_lib_lock = threading.Lock()
_u64p = ctypes.POINTER(ctypes.c_uint64)


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = _native.load("tfrecord_codec.cpp", "tfrecord")
        lib.tfrec_crc32c.restype = ctypes.c_uint32
        lib.tfrec_crc32c.argtypes = (ctypes.c_char_p, ctypes.c_uint64)
        lib.tfrec_masked_crc32c.restype = ctypes.c_uint32
        lib.tfrec_masked_crc32c.argtypes = (ctypes.c_char_p, ctypes.c_uint64)
        lib.tfrec_index.restype = ctypes.c_int64
        lib.tfrec_index.argtypes = (
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
            _u64p, _u64p, ctypes.c_uint64)
        for fn, outp in ((lib.tfrec_batch_floats,
                          ctypes.POINTER(ctypes.c_float)),
                         (lib.tfrec_batch_int64,
                          ctypes.POINTER(ctypes.c_int64))):
            fn.restype = ctypes.c_int64
            fn.argtypes = (ctypes.c_void_p, _u64p, _u64p, ctypes.c_uint64,
                           ctypes.c_char_p, ctypes.c_uint64, outp,
                           ctypes.c_uint64)
        _lib = lib
        return _lib


def available():
    """True when the native codec builds/loads on this host."""
    try:
        _load()
        return True
    except Exception as e:  # noqa: BLE001 - degrade to pure python
        logger.debug("native tfrecord codec unavailable: %s", e)
        return False


def crc32c(data):
    return _load().tfrec_crc32c(bytes(data), len(data))


def masked_crc32c(data):
    return _load().tfrec_masked_crc32c(bytes(data), len(data))


_ERRORS = {-1: "truncated TFRecord", -2: "corrupt TFRecord: bad length crc",
           -3: "corrupt TFRecord: bad data crc"}


def _addr(mv):
    """Base address of a (possibly read-only) buffer. numpy keeps the
    view alive via the returned array's .base; callers hold mv anyway."""
    return ctypes.c_void_p(np.frombuffer(mv, np.uint8).ctypes.data)


def index_buffer(buf, verify_crc=True):
    """Validate framing over a whole-file buffer; return (offsets, lengths)
    uint64 arrays addressing each record's payload within ``buf``."""
    mv = memoryview(buf)
    n = mv.nbytes
    if n == 0:
        return np.empty(0, np.uint64), np.empty(0, np.uint64)
    # every record costs >= 16 framing+payload bytes
    cap = n // 16 + 1
    offsets = np.empty(cap, np.uint64)
    lengths = np.empty(cap, np.uint64)
    base = _addr(mv)
    count = _load().tfrec_index(
        base, n, 1 if verify_crc else 0,
        offsets.ctypes.data_as(_u64p), lengths.ctypes.data_as(_u64p), cap)
    if count < 0:
        raise ValueError(_ERRORS.get(count, "TFRecord scan error %d" % count))
    return offsets[:count], lengths[:count]


def iter_records(buf, verify_crc=True):
    """Yield zero-copy memoryview payload slices from a file buffer."""
    mv = memoryview(buf)
    offsets, lengths = index_buffer(mv, verify_crc)
    for off, ln in zip(offsets.tolist(), lengths.tolist()):
        yield mv[off:off + ln]


def _batch(buf, offsets, lengths, name, width, dtype):
    mv = memoryview(buf)
    m = len(offsets)
    out = np.empty((m, width), dtype)
    if m == 0:
        return out
    name_b = name.encode("utf-8")
    base = _addr(mv)
    lib = _load()
    offs = np.ascontiguousarray(offsets, np.uint64)
    lens = np.ascontiguousarray(lengths, np.uint64)
    if dtype == np.float32:
        rc = lib.tfrec_batch_floats(
            base, offs.ctypes.data_as(_u64p), lens.ctypes.data_as(_u64p),
            m, name_b, len(name_b),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), width)
    else:
        rc = lib.tfrec_batch_int64(
            base, offs.ctypes.data_as(_u64p), lens.ctypes.data_as(_u64p),
            m, name_b, len(name_b),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), width)
    if rc != 0:
        raise ValueError(
            "record %d: feature %r missing, wrong kind, or not %d values"
            % (-rc - 1, name, width))
    return out


def batch_floats(buf, offsets, lengths, name, width):
    """[m, width] float32 of feature ``name`` across the indexed records."""
    return _batch(buf, offsets, lengths, name, width, np.float32)


def batch_int64(buf, offsets, lengths, name, width):
    """[m, width] int64 of feature ``name`` across the indexed records."""
    return _batch(buf, offsets, lengths, name, width, np.int64)
