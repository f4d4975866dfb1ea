"""ctypes bindings for the native C++ data loader (native/dataloader.cc).

The C++ tier replaces what the reference got from TensorFlow's native input
runtime (queue runners / tf.data C++, SURVEY.md L0-L1): CRC32C, CIFAR binary
parsing, and a multithreaded TFRecord prefetcher with a bounded ring buffer.

The library is built from the checkout's own sources: the first load of a
process compares a hash of ``dataloader.cc`` + ``Makefile`` with the one
recorded next to ``libdrtdata.so`` at its last build and rebuilds on any
difference, so a ``.so`` left in the working tree from other sources is
never used as is. A build or load that fails raises
:class:`NativeUnavailable` with the reason; the pure-python paths
(data/cifar.py, data/tfrecord.py) are behavior-identical (tests assert
this), but taking them is the caller's decision, never a silent default.
"""
from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import subprocess
from typing import Iterator, List, Optional, Tuple

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "native"))
_SO_PATH = os.path.join(_NATIVE_DIR, "libdrtdata.so")
#: hash of the sources libdrtdata.so was last built from (ignored by git
#: with the .so itself — native/.gitignore)
_STAMP_PATH = _SO_PATH + ".srchash"
_SOURCES = ("dataloader.cc", "Makefile")
_lib = None


class NativeUnavailable(RuntimeError):
    pass


def _source_hash() -> str:
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_from(src_hash: str) -> bool:
    """True iff libdrtdata.so exists and its stamp names ``src_hash``."""
    try:
        with open(_STAMP_PATH) as f:
            return os.path.exists(_SO_PATH) and f.read().strip() == src_hash
    except OSError:
        return False


def _build(src_hash: str) -> None:
    """``make -B`` the library and stamp it; raises with the tool's output."""
    try:
        subprocess.run(["make", "-B", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, text=True, timeout=300)
    except subprocess.CalledProcessError as e:
        raise NativeUnavailable(
            f"building {_SO_PATH} failed (rc {e.returncode}):\n"
            f"{(e.stderr or e.stdout or '').strip()[-2000:]}") from e
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeUnavailable(
            f"building {_SO_PATH} failed: {e!r} (needs make and a C++ "
            "compiler)") from e
    with open(_STAMP_PATH, "w") as f:  # under load_library's lock
        f.write(src_hash + "\n")
    log.info("native loader built from the checkout's sources: %s", _SO_PATH)


def load_library() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    # staleness is decided BEFORE the first dlopen: glibc caches handles by
    # device/inode and make relinks in place, so once an old mapping exists
    # a rebuild + re-CDLL hands back the stale symbol table
    src_hash = _source_hash()
    # one builder at a time: a launcher starts several processes per host,
    # and a peer must not dlopen a half-linked file
    with open(_SO_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not _built_from(src_hash):
            _build(src_hash)
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        raise NativeUnavailable(f"{_SO_PATH} failed to load: {e}") from e
    lib.drt_crc32c.restype = ctypes.c_uint32
    lib.drt_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.drt_masked_crc32c.restype = ctypes.c_uint32
    lib.drt_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.drt_cifar_load.restype = ctypes.c_int64
    lib.drt_cifar_load.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int64]
    lib.drt_prefetch_create.restype = ctypes.c_void_p
    lib.drt_prefetch_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32]
    lib.drt_prefetch_next.restype = ctypes.c_int64
    lib.drt_prefetch_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64)]
    lib.drt_prefetch_crc_errors.restype = ctypes.c_int64
    lib.drt_prefetch_crc_errors.argtypes = [ctypes.c_void_p]
    lib.drt_prefetch_truncated.restype = ctypes.c_int64
    lib.drt_prefetch_truncated.argtypes = [ctypes.c_void_p]
    lib.drt_prefetch_stop.restype = None
    lib.drt_prefetch_stop.argtypes = [ctypes.c_void_p]
    lib.drt_prefetch_destroy.restype = None
    lib.drt_prefetch_destroy.argtypes = [ctypes.c_void_p]
    lib.drt_has_jpeg.restype = ctypes.c_int
    lib.drt_has_jpeg.argtypes = []
    if lib.drt_has_jpeg():
        lib.drt_decode_resize_crop.restype = ctypes.c_int
        lib.drt_decode_resize_crop.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8)]
    _lib = lib
    return lib


def native_available() -> bool:
    """False — with the reason logged at WARNING — when the library cannot
    be built or loaded here."""
    try:
        load_library()
        return True
    except NativeUnavailable as e:
        log.warning("native loader unavailable: %s", e)
        return False


def crc32c(data: bytes) -> int:
    return load_library().drt_crc32c(data, len(data))


def masked_crc32c(data: bytes) -> int:
    return load_library().drt_masked_crc32c(data, len(data))


def load_cifar_native(path: str, label_bytes: int, label_offset: int,
                      max_records: int = 0
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """CIFAR binary file → (HWC uint8 images, int32 labels), parsed in C++.

    ``max_records`` 0 (default) sizes the buffers from the file itself, so
    files larger than the standard 60k-record datasets load in full —
    identical output to the python parser, which has no cap."""
    lib = load_library()
    if max_records <= 0:
        record_len = label_bytes + 32 * 32 * 3
        max_records = max(1, os.path.getsize(path) // record_len)
    images = np.empty((max_records, 32, 32, 3), np.uint8)
    labels = np.empty((max_records,), np.int32)
    n = lib.drt_cifar_load(
        path.encode(), label_bytes, label_offset,
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        max_records)
    if n < 0:
        raise FileNotFoundError(path)
    return images[:n].copy(), labels[:n].copy()


class NativePrefetcher:
    """Iterate raw TFRecord payloads produced by C++ reader threads.

    Thread contract: one consumer thread iterates; ``close()`` may run
    from another thread (teardown, __del__). Protocol: close() nulls the
    handle under ``_lock`` (no NEW C calls can start), calls
    ``drt_prefetch_stop`` (wakes a consumer BLOCKED inside
    ``drt_prefetch_next`` — the stop flag satisfies its wait predicate),
    waits for the in-flight counter to drain, and only then destroys —
    so the native object is never freed under a live call and close()
    never waits on data arrival. A damaged shard is LOUD: mid-record
    truncation raises IOError at end of stream (matching
    data/tfrecord.py), and skipped-CRC records warn."""

    def __init__(self, paths: List[str], num_threads: int = 4,
                 capacity: int = 512, verify_crc: bool = False):
        import threading
        self._lock = threading.Lock()  # first: __del__ may see a partial init
        self._inflight = 0
        self._handle = None
        self._final_crc_errors = 0
        self._final_truncated = 0
        self._lib = load_library()
        arr = (ctypes.c_char_p * len(paths))(*[p.encode() for p in paths])

        def create():
            handle = self._lib.drt_prefetch_create(
                arr, len(paths), num_threads, capacity, int(verify_crc))
            if not handle:
                raise NativeUnavailable("prefetcher creation failed")
            return handle

        # bounded retry (resilience/retry.py): creation opens every shard,
        # and a transient FS hiccup there shouldn't abort the whole run —
        # persistent failure still raises NativeUnavailable for the
        # documented python fallback
        from ..resilience.retry import retry_call
        self._handle = retry_call(
            create, retries=2, base_delay=0.1,
            retry_on=(NativeUnavailable,),
            description="native prefetcher open")
        self._buf = np.empty(1 << 20, np.uint8)  # 1 MB, grown on demand

    def __iter__(self) -> Iterator[bytes]:
        return self

    def __next__(self) -> bytes:
        while True:
            with self._lock:
                if self._handle is None:
                    raise StopIteration
                self._inflight += 1
                h = self._handle
            truncated = crc = 0
            try:
                needed = ctypes.c_int64(0)
                n = self._lib.drt_prefetch_next(
                    h,
                    self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    self._buf.size, ctypes.byref(needed))
                if n == 0:  # end of stream: read the error counters while
                    truncated = self._lib.drt_prefetch_truncated(h)
                    crc = self._lib.drt_prefetch_crc_errors(h)  # h is live
            finally:
                with self._lock:
                    self._inflight -= 1
            if n == 0:
                if crc:
                    log.warning("native prefetcher skipped %d record(s) "
                                "with bad CRC", crc)
                if truncated:
                    raise IOError(
                        f"truncated/corrupt TFRecord framing in {truncated} "
                        "file(s) — stream is incomplete (the python reader "
                        "raises the same way)")
                raise StopIteration
            if n == -1:
                self._buf = np.empty(int(needed.value) * 2, np.uint8)
                continue
            return bytes(self._buf[:n])

    @property
    def crc_errors(self) -> int:
        with self._lock:
            if self._handle is None:
                return self._final_crc_errors
            return self._lib.drt_prefetch_crc_errors(self._handle)

    @property
    def truncated(self) -> int:
        with self._lock:
            if self._handle is None:
                return self._final_truncated
            return self._lib.drt_prefetch_truncated(self._handle)

    def close(self, drain_timeout: float = 5.0) -> None:
        import time
        with self._lock:
            h, self._handle = self._handle, None
        if h is None:
            return
        # wake a consumer blocked inside drt_prefetch_next; it returns 0
        # and decrements _inflight (its properties reads use the local h,
        # still alive until destroy below)
        self._lib.drt_prefetch_stop(h)
        deadline = time.monotonic() + drain_timeout
        while True:
            with self._lock:
                if self._inflight == 0:
                    break
            if time.monotonic() >= deadline:
                # a missed wakeup in the native layer must not turn
                # teardown (incl. __del__ at interpreter exit) into an
                # infinite hang: leak the native object — destroying it
                # under a live drt_prefetch_next call would be a
                # use-after-free (ADVICE r5)
                with self._lock:
                    inflight = self._inflight
                log.warning(
                    "NativePrefetcher.close(): %d in-flight native call(s) "
                    "did not drain within %.1fs; leaking the native "
                    "prefetcher handle instead of risking a use-after-free",
                    inflight, drain_timeout)
                self._final_crc_errors = self._lib.drt_prefetch_crc_errors(h)
                self._final_truncated = self._lib.drt_prefetch_truncated(h)
                return
            time.sleep(0.001)
        self._final_crc_errors = self._lib.drt_prefetch_crc_errors(h)
        self._final_truncated = self._lib.drt_prefetch_truncated(h)
        self._lib.drt_prefetch_destroy(h)

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def native_jpeg_available() -> bool:
    """True iff the .so was built against libjpeg (drt_has_jpeg)."""
    try:
        lib = load_library()
        return bool(lib.drt_has_jpeg())
    except NativeUnavailable:
        return False


def decode_resize_crop_native(data: bytes, resize_side: int, top: int,
                              left: int, out_size: int, flip: bool
                              ) -> Optional[np.ndarray]:
    """Fused C++ ImageNet transform: DCT-scaled JPEG decode + bilinear
    sample of exactly the (out_size², 3) crop window at (top, left) of the
    conceptual resized image, flipped when asked. The ctypes call releases
    the GIL, so a Python thread pool around this decodes in true parallel.
    Returns None when the content needs the PIL fallback (non-JPEG, CMYK,
    corrupt) or the library lacks libjpeg."""
    try:
        lib = load_library()
    except NativeUnavailable:
        return None
    if not lib.drt_has_jpeg():
        return None
    out = np.empty((out_size, out_size, 3), np.uint8)
    rc = lib.drt_decode_resize_crop(
        data, len(data), resize_side, top, left, out_size, int(flip),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out if rc == 0 else None
