"""ctypes bindings for the native runtime, with pure-Python fallbacks.

The shared library is built from ``runtime/native`` with the checked-in
Makefile.  Every load runs ``make`` first (a no-op when the library is
up to date), so what is loaded is what the committed source builds —
never a stale binary left on disk.  Every native function has an
identical-semantics Python twin for machines with no toolchain; taking
them is logged once at WARNING with the compiler's message, and
callers that need the native speed check :func:`native_available`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import zlib
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from dryad_tpu.columnar.schema import hash64_bytes, split64
from dryad_tpu.utils.logging import get_logger

log = get_logger("dryad_tpu.runtime")

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdryadnative.so")
_U32P = ctypes.POINTER(ctypes.c_uint32)
_U64P = ctypes.POINTER(ctypes.c_uint64)
_lib = None
_lib_tried = False
_lock = threading.Lock()


def build_native(force: bool = False) -> Optional[str]:
    """Run the checked-in Makefile (``force``: rebuild unconditionally).
    Returns None on success, else the toolchain's message."""
    cmd = ["make", "-C", _NATIVE_DIR] + (["-B"] if force else [])
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=300)
    except subprocess.CalledProcessError as e:
        err = (e.stderr or b"").decode("utf-8", "replace").strip()
        return f"{' '.join(cmd)} exited {e.returncode}: {err}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{' '.join(cmd)}: {e!r}"
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        err = build_native()
        if err is not None:
            log.warning(
                "native build failed; using Python fallbacks: %s", err
            )
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            log.warning("native load failed (%s); using Python fallbacks", e)
            return None
        lib.dn_hash64.restype = ctypes.c_uint64
        lib.dn_hash64.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.dn_words_open.restype = ctypes.c_void_p
        lib.dn_words_open.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, _U64P, ctypes.c_size_t,
        ]
        lib.dn_words_tokens.restype = ctypes.c_size_t
        lib.dn_words_tokens.argtypes = [ctypes.c_void_p]
        lib.dn_words_fill.restype = ctypes.c_size_t
        lib.dn_words_fill.argtypes = [ctypes.c_void_p, _U32P, _U32P, _U32P, _U32P]
        lib.dn_words_distinct.restype = None
        lib.dn_words_distinct.argtypes = [
            ctypes.c_void_p, _U64P, _U64P, _U64P, _U32P,
        ]
        lib.dn_words_close.restype = None
        lib.dn_words_close.argtypes = [ctypes.c_void_p]
        lib.dn_channel_open.restype = ctypes.c_void_p
        lib.dn_channel_open.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_size_t,
        ]
        lib.dn_channel_next.restype = ctypes.c_int64
        lib.dn_channel_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ]
        lib.dn_channel_close.restype = None
        lib.dn_channel_close.argtypes = [ctypes.c_void_p]
        lib.dn_write_partition.restype = ctypes.c_int32
        lib.dn_write_partition.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64, ctypes.c_int32,
        ]
        lib.dn_fifo_create.restype = ctypes.c_void_p
        lib.dn_fifo_create.argtypes = [ctypes.c_size_t]
        lib.dn_fifo_push.restype = ctypes.c_int32
        lib.dn_fifo_push.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.dn_fifo_pop.restype = ctypes.c_int64
        lib.dn_fifo_pop.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ]
        lib.dn_fifo_close.restype = None
        lib.dn_fifo_close.argtypes = [ctypes.c_void_p]
        lib.dn_fifo_destroy.restype = None
        lib.dn_fifo_destroy.argtypes = [ctypes.c_void_p]
        lib.dn_tlv_encode.restype = ctypes.c_size_t
        lib.dn_tlv_encode.argtypes = [
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint16),
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.dn_tlv_encoded_size.restype = ctypes.c_size_t
        lib.dn_tlv_encoded_size.argtypes = [
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.dn_tlv_decode.restype = ctypes.c_size_t
        lib.dn_tlv_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint16), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
        ]
        lib.dn_decompress_batch.restype = ctypes.c_int32
        lib.dn_decompress_batch.argtypes = [
            ctypes.c_size_t, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        _lib = lib
        log.info("native runtime loaded from %s", _LIB_PATH)
        return _lib


def native_available() -> bool:
    return _load() is not None


def decompress_batch(srcs, dsts) -> bool:
    """Inflate each zlib payload in ``srcs`` into the matching writable
    buffer in ``dsts`` (numpy arrays), all columns in parallel on native
    threads (the read half of the channel codec,
    ``channelbuffernativereader.cpp`` analog).  Returns False when the
    native runtime is unavailable (caller falls back to zlib)."""
    lib = _load()
    if lib is None or not srcs:
        return False
    n = len(srcs)
    src_ptrs = (ctypes.c_void_p * n)()
    src_lens = (ctypes.c_uint64 * n)()
    dst_ptrs = (ctypes.c_void_p * n)()
    dst_lens = (ctypes.c_uint64 * n)()
    for i, (s, d) in enumerate(zip(srcs, dsts)):
        # c_char_p points at the bytes object's buffer (no copy); srcs
        # stays referenced by the caller for the duration of the call
        src_ptrs[i] = ctypes.cast(ctypes.c_char_p(s), ctypes.c_void_p)
        src_lens[i] = len(s)
        dst_ptrs[i] = d.ctypes.data_as(ctypes.c_void_p)
        dst_lens[i] = d.nbytes
    rc = lib.dn_decompress_batch(n, src_ptrs, src_lens, dst_ptrs, dst_lens)
    if rc != 0:
        raise ValueError("corrupt compressed column payload")
    return True


def hash64(data: bytes) -> int:
    lib = _load()
    if lib is not None:
        return int(lib.dn_hash64(data, len(data)))
    return hash64_bytes(data)


class Tokens(NamedTuple):
    """A text buffer's tokens as columns, and its distinct words."""

    h0: np.ndarray  # a token: Hash64 low / high word
    h1: np.ndarray
    r0: np.ndarray  # a token: prefix rank of bytes 0-4 / 4-8
    r1: np.ndarray
    # a distinct word, in order of first occurrence: its 64-bit hash,
    # the index of its first token, that token's byte offset and length
    hashes: np.ndarray
    first: np.ndarray
    starts: np.ndarray
    lens: np.ndarray
    runs: int  # cuts of the buffer tokenized side by side


# The tokenizer takes a thread for every _TOKENIZE_RUN_BYTES of text, up
# to _TOKENIZE_THREADS: a buffer of a few words (tests, the serving
# tier) is one run on the caller's thread, a corpus is cut at whitespace
# and its runs are hashed side by side, each writing its own stretch of
# the columns (one thread writes newly mapped pages at 0.9 GB/s on the
# chip's host, PERF.md section 6, PR 34) and finding its own distinct
# words.
_TOKENIZE_THREADS = 8
_TOKENIZE_RUN_BYTES = 1 << 20


def tokenize(text: bytes, cuts: Optional[Sequence[int]] = None) -> Tokens:
    """Whitespace-tokenize a byte buffer in one pass: the four columns
    a token and the distinct words, found in a hash table as the tokens
    are hashed (nothing sorts).  ``cuts`` proposes the byte offsets at
    which the buffer is cut into runs (each moves on to the next
    whitespace); left out, the buffer's length decides how many."""
    lib = _load()
    if lib is None:
        return _tokenize_py(text)
    if cuts is None:
        runs = max(1, min(_TOKENIZE_THREADS, len(text) // _TOKENIZE_RUN_BYTES))
        cuts = [len(text) * r // runs for r in range(1, runs)]
    handle = lib.dn_words_open(
        text, len(text), (ctypes.c_uint64 * len(cuts))(*cuts), len(cuts) + 1
    )
    try:
        n = lib.dn_words_tokens(handle)
        cols = [np.empty(n, np.uint32) for _ in range(4)]
        distinct = lib.dn_words_fill(
            handle, *(c.ctypes.data_as(_U32P) for c in cols)
        )
        hashes, first, starts = (
            np.empty(distinct, np.uint64) for _ in range(3)
        )
        lens = np.empty(distinct, np.uint32)
        lib.dn_words_distinct(
            handle, hashes.ctypes.data_as(_U64P), first.ctypes.data_as(_U64P),
            starts.ctypes.data_as(_U64P), lens.ctypes.data_as(_U32P),
        )
    finally:
        lib.dn_words_close(handle)
    return Tokens(*cols, hashes, first, starts, lens, len(cuts) + 1)


def _tokenize_py(text: bytes) -> Tokens:
    """:func:`tokenize` without the native library: one run."""
    hashes, ranks, words = [], [], {}
    i = 0
    while i < len(text):
        while i < len(text) and text[i : i + 1].isspace():
            i += 1
        if i >= len(text):
            break
        s = i
        while i < len(text) and not text[i : i + 1].isspace():
            i += 1
        token = text[s:i]
        h = hash64_bytes(token)
        words.setdefault(h, (len(hashes), s, i - s))
        hashes.append(h)
        ranks.append(int.from_bytes(token[:8].ljust(8, b"\0"), "big"))
    h0, h1 = split64(np.array(hashes, np.uint64))
    r1, r0 = split64(np.array(ranks, np.uint64))
    where = np.array(list(words.values()), np.uint64).reshape(-1, 3)
    return Tokens(
        h0, h1, r0, r1, np.array(list(words), np.uint64),
        where[:, 0], where[:, 1], where[:, 2].astype(np.uint32), 1,
    )


def write_partition(
    path: str, cols: "dict[str, np.ndarray]", compression: Optional[str] = None
) -> None:
    """Write one ``.dpf`` partition file (format: ``columnar/io.py``).

    Native path compresses columns concurrently on a thread pool (the
    async channel-writer analog); falls back to the Python writer.
    """
    lib = _load()
    if lib is None:
        from dryad_tpu.columnar import io as cio

        cio.write_partition_file(path, cols, compression)
        return
    names = list(cols.keys())
    arrays = [np.ascontiguousarray(cols[n]) for n in names]
    rows = len(arrays[0]) if arrays else 0
    name_arr = (ctypes.c_char_p * len(names))(*[n.encode() for n in names])
    dt_arr = (ctypes.c_char_p * len(names))(
        *[str(a.dtype).encode() for a in arrays]
    )
    buf_arr = (ctypes.c_void_p * len(names))(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays]
    )
    len_arr = (ctypes.c_uint64 * len(names))(*[a.nbytes for a in arrays])
    level = 6 if (compression or "none") == "zlib" else -1
    rc = lib.dn_write_partition(
        path.encode(), len(names), name_arr, dt_arr, buf_arr, len_arr,
        rows, level,
    )
    if rc != 0:
        raise IOError(f"native partition write failed rc={rc} path={path}")


class Fifo:
    """Bounded blocking byte-block queue (reference RChannelFifo,
    ``channelfifo.h:31-136``): the in-process channel between pipelined
    producer/consumer threads, with latch flow control.

    Semantics (both backends): ``push`` blocks while full, returns False
    once closed; ``pop`` blocks until a block or close, then returns
    None at end-of-stream (repeatably); ``close`` never blocks.
    """

    def __init__(self, depth: int = 4):
        self._lib = _load()
        # The native pop hands out a pointer into a buffer owned by the
        # channel that is only valid until the next pop — serialize
        # pop+copy so concurrent consumers can't invalidate it.
        self._pop_lock = threading.Lock()
        if self._lib is not None:
            self._handle = self._lib.dn_fifo_create(depth)
        else:
            self._handle = None
            self._depth = max(1, depth)
            self._deque: List[bytes] = []
            self._closed = False
            self._cv = threading.Condition()

    def push(self, data: bytes) -> bool:
        if self._handle is not None:
            return self._lib.dn_fifo_push(self._handle, data, len(data)) == 0
        with self._cv:
            while not self._closed and len(self._deque) >= self._depth:
                self._cv.wait()
            if self._closed:
                return False
            self._deque.append(data)
            self._cv.notify_all()
            return True

    def pop(self) -> Optional[bytes]:
        """Next block, or None at end of stream (writer closed + drained)."""
        if self._handle is not None:
            with self._pop_lock:
                ptr = ctypes.POINTER(ctypes.c_uint8)()
                n = self._lib.dn_fifo_pop(self._handle, ctypes.byref(ptr))
                if n < 0:
                    return None
                return ctypes.string_at(ptr, n)
        with self._cv:
            while not self._closed and not self._deque:
                self._cv.wait()
            if not self._deque:
                return None
            item = self._deque.pop(0)
            self._cv.notify_all()
            return item

    def close(self) -> None:
        if self._handle is not None:
            self._lib.dn_fifo_close(self._handle)
            return
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def destroy(self) -> None:
        if self._handle is not None:
            self._lib.dn_fifo_destroy(self._handle)
            self._handle = None


def tlv_encode(entries: List[Tuple[int, bytes]]) -> bytes:
    """Encode (tag, value) pairs as the TLV property wire format
    (reference property blocks, ``gang/DrProperty.cpp``):
    tag u16 LE + len u32 LE + value."""
    for tag, val in entries:
        if not 0 <= tag <= 0xFFFF:
            raise ValueError(f"TLV tag {tag} outside u16 range")
        if len(val) > 0xFFFFFFFF:
            raise ValueError("TLV value exceeds u32 length")
    lib = _load()
    if lib is not None and entries:
        tags = (ctypes.c_uint16 * len(entries))(*[t for t, _ in entries])
        vals = [v for _, v in entries]
        lens = (ctypes.c_uint32 * len(entries))(*[len(v) for v in vals])
        ptrs = (ctypes.c_void_p * len(entries))(
            *[ctypes.cast(ctypes.c_char_p(v), ctypes.c_void_p).value
              for v in vals]
        )
        size = lib.dn_tlv_encoded_size(len(entries), lens)
        out = ctypes.create_string_buffer(size)
        got = lib.dn_tlv_encode(len(entries), tags, ptrs, lens, out, size)
        if got != size:
            raise ValueError("tlv encode overflow")
        return out.raw
    import struct

    parts = []
    for tag, val in entries:
        parts.append(struct.pack("<HI", tag, len(val)))
        parts.append(val)
    return b"".join(parts)


def tlv_decode(buf: bytes) -> List[Tuple[int, bytes]]:
    """Decode a TLV property block; raises ValueError on malformed input."""
    lib = _load()
    if lib is not None and buf:
        max_n = max(1, len(buf) // 6)
        tags = (ctypes.c_uint16 * max_n)()
        offs = (ctypes.c_uint64 * max_n)()
        lens = (ctypes.c_uint32 * max_n)()
        n = lib.dn_tlv_decode(buf, len(buf), max_n, tags, offs, lens)
        if n == ctypes.c_size_t(-1).value:
            raise ValueError("malformed TLV block")
        return [
            (int(tags[i]), buf[offs[i] : offs[i] + lens[i]]) for i in range(n)
        ]
    import struct

    out = []
    at = 0
    while at < len(buf):
        if at + 6 > len(buf):
            raise ValueError("malformed TLV block")
        tag, ln = struct.unpack_from("<HI", buf, at)
        if at + 6 + ln > len(buf):
            raise ValueError("malformed TLV block")
        out.append((tag, buf[at + 6 : at + 6 + ln]))
        at += 6 + ln
    return out


class PrefetchChannel:
    """Ordered multi-file reader with background prefetch.

    The analog of the reference's async channel buffer readers; iterate
    to get each file's bytes in order.
    """

    def __init__(self, paths: List[str], depth: int = 4, threads: int = 2):
        self.paths = list(paths)
        self._lib = _load()
        self._handle = None
        self._fallback_iter = None
        if self._lib is not None:
            arr = (ctypes.c_char_p * len(self.paths))(
                *[p.encode() for p in self.paths]
            )
            self._handle = self._lib.dn_channel_open(
                arr, len(self.paths), depth, threads
            )

    def __iter__(self):
        if self._handle is not None:
            ptr = ctypes.POINTER(ctypes.c_uint8)()
            while True:
                n = self._lib.dn_channel_next(self._handle, ctypes.byref(ptr))
                if n == -1:
                    break
                if n == -2:
                    raise IOError("native channel read error")
                yield ctypes.string_at(ptr, n)
        else:
            for p in self.paths:
                with open(p, "rb") as fh:
                    yield fh.read()

    def close(self) -> None:
        if self._handle is not None:
            self._lib.dn_channel_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
