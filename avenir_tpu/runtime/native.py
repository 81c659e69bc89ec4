"""ctypes bridge to the C++ data plane (runtime/native/csv_encode.cpp).

Compiles the shared library on first use (g++, kept next to the source,
never committed; rebuilt when the source is newer) and exposes :func:`encode_bytes` — CSV
bytes → :class:`EncodedDataset` with semantics identical to
``DatasetEncoder.transform``. All callers must treat this as an optional fast
path: :func:`is_available` gates it, and ``DatasetEncoder`` stays the
portable reference implementation.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "native", "csv_encode.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _lib_path() -> str:
    """Where the compiled library lives: next to the source when that
    directory is writable (repo checkouts; the .so is git-ignored and
    always built from the checkout's own csv_encode.cpp on first use),
    else a per-user cache dir (pip installs into read-only site-packages
    must not silently lose the native fast path). The cache filename
    embeds a hash of the source so a package upgrade can never be served
    a stale-ABI build (mtime comparison is unreliable there — wheel
    extraction preserves archive timestamps)."""
    pkg_dir = os.path.join(os.path.dirname(__file__), "native")
    if os.access(pkg_dir, os.W_OK):
        return os.path.join(pkg_dir, "libavenir_native.so")
    import hashlib
    with open(_SRC, "rb") as fh:
        tag = hashlib.sha1(fh.read()).hexdigest()[:12]
    cache = os.path.join(os.path.expanduser("~"), ".cache", "avenir_tpu",
                         "native")
    os.makedirs(cache, exist_ok=True)
    return os.path.join(cache, f"libavenir_native-{tag}.so")


try:
    _LIB: Optional[str] = _lib_path()
except OSError as e:                   # e.g. unwritable/absent HOME: the
    _LIB = None                        # native path is OPTIONAL — degrade,
    _build_error = str(e)              # never crash the import

_ERRORS = {
    -1: "ragged CSV record",
    -2: "unparseable numeric field",
    -3: "unknown class label",
    -4: "row buffer overflow",
}

KIND_CATEGORICAL, KIND_BINNED_NUMERIC, KIND_CONTINUOUS, KIND_LABEL, KIND_ID = \
    0, 1, 2, 3, 4


def _build() -> Optional[ctypes.CDLL]:
    global _build_error
    if _LIB is None:                   # no writable location for the build
        return None
    if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
        return ctypes.CDLL(_LIB)
    # two processes importing concurrently must not both write the .so:
    # serialize builders on a lock, compile to a temp path, publish with an
    # atomic rename, and re-check under the lock (the loser just loads)
    from avenir_tpu.utils.locking import FileLock, LockHeldError

    try:
        with FileLock(_LIB, timeout_s=150.0):
            if os.path.exists(_LIB) and \
                    os.path.getmtime(_LIB) >= os.path.getmtime(_SRC):
                return ctypes.CDLL(_LIB)
            tmp = _LIB + ".build"
            try:
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-pthread",
                     "-std=c++17", "-o", tmp, _SRC],
                    check=True, capture_output=True, text=True, timeout=120)
                os.replace(tmp, _LIB)
            except BaseException:
                try:
                    os.unlink(tmp)     # no partial artifact on failure
                except OSError:
                    pass
                raise
    except LockHeldError as e:
        _build_error = str(e)
        return None
    except (OSError, subprocess.SubprocessError) as e:
        _build_error = getattr(e, "stderr", None) or str(e)
        return None
    return ctypes.CDLL(_LIB)


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    with _lock:
        if _lib is None and _build_error is None:
            lib = _build()
            if lib is not None:
                i32p = ctypes.POINTER(ctypes.c_int32)
                lib.avenir_csv_encode.restype = ctypes.c_long
                lib.avenir_csv_encode.argtypes = [
                    ctypes.c_char_p, ctypes.c_long, ctypes.c_char, ctypes.c_int32,
                    i32p, i32p,
                    ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_int64),
                    i32p, ctypes.c_int32, ctypes.c_char_p,
                    i32p, ctypes.c_long,
                    ctypes.POINTER(ctypes.c_float), ctypes.c_long,
                    i32p,
                    ctypes.POINTER(ctypes.c_int64), i32p,
                    ctypes.c_long, ctypes.POINTER(ctypes.c_long),
                ]
                lib.avenir_csv_encode_mt.restype = ctypes.c_long
                lib.avenir_csv_encode_mt.argtypes = \
                    lib.avenir_csv_encode.argtypes + [ctypes.c_int32]
                lib.avenir_csv_count_rows.restype = ctypes.c_long
                lib.avenir_csv_count_rows.argtypes = [ctypes.c_char_p, ctypes.c_long]
                lib.avenir_gather_ids_u32.restype = ctypes.c_int32
                lib.avenir_gather_ids_u32.argtypes = [
                    ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                    i32p, ctypes.c_long, ctypes.POINTER(ctypes.c_uint32),
                    ctypes.c_int32,
                ]
                _lib = lib
        return _lib


def is_available() -> bool:
    return _get_lib() is not None


def build_error() -> Optional[str]:
    _get_lib()
    return _build_error


def _specs_from_encoder(encoder, with_labels: bool = True) -> tuple:
    """Flatten a fitted DatasetEncoder into the parallel spec arrays."""
    kinds: List[int] = []
    ordinals: List[int] = []
    widths: List[float] = []
    offsets: List[int] = []
    nbins: List[int] = []
    vocab_parts: List[bytes] = []
    for f in encoder.binned_fields:
        ordinals.append(f.ordinal)
        if f.is_categorical:
            kinds.append(KIND_CATEGORICAL)
            widths.append(0.0)
            offsets.append(0)
            nbins.append(encoder.n_bins[f.ordinal])
            vocab = sorted(encoder.vocab[f.ordinal].items(), key=lambda kv: kv[1])
            vocab_parts.append(
                b"".join(v.encode() + b"\x1f" for v, _ in vocab) + b"\x1e")
        else:
            kinds.append(KIND_BINNED_NUMERIC)
            widths.append(float(f.bucket_width))
            offsets.append(int(encoder.bin_offset[f.ordinal]))
            nbins.append(encoder.n_bins[f.ordinal])
    for f in encoder.cont_fields:
        kinds.append(KIND_CONTINUOUS)
        ordinals.append(f.ordinal)
        widths.append(0.0)
        offsets.append(0)
        nbins.append(0)
    if with_labels and encoder.class_field is not None and encoder.class_values:
        kinds.append(KIND_LABEL)
        ordinals.append(encoder.class_field.ordinal)
        widths.append(0.0)
        offsets.append(0)
        nbins.append(len(encoder.class_values))
        vocab_parts.append(
            b"".join(v.encode() + b"\x1f" for v in encoder.class_values) + b"\x1e")
    if encoder.id_field is not None:
        kinds.append(KIND_ID)
        ordinals.append(encoder.id_field.ordinal)
        widths.append(0.0)
        offsets.append(0)
        nbins.append(0)
    return (np.asarray(kinds, np.int32), np.asarray(ordinals, np.int32),
            np.asarray(widths, np.float64), np.asarray(offsets, np.int64),
            np.asarray(nbins, np.int32), b"".join(vocab_parts))


def encode_bytes(data: bytes, encoder, ncols: int, delim: str = ",",
                 with_labels: bool = True, nthreads: Optional[int] = None):
    """CSV bytes → EncodedDataset via the native kernel.

    ``encoder`` must be a fitted DatasetEncoder; raises ValueError on data
    errors (same conditions as the Python path) and RuntimeError if the
    native library is unavailable. Buffers over 1 MiB are parsed by
    ``nthreads`` worker threads (default: up to 8 or the CPU count) with
    output identical to the single-threaded path.
    """
    from avenir_tpu.core.encoding import EncodedDataset

    lib = _get_lib()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    kinds, ordinals, widths, offsets, nbins, vocab_blob = \
        _specs_from_encoder(encoder, with_labels=with_labels)
    n_binned = len(encoder.binned_fields)
    n_cont = len(encoder.cont_fields)
    max_rows = lib.avenir_csv_count_rows(data, len(data))
    codes = np.zeros((max_rows, max(n_binned, 1)), np.int32)
    cont = np.zeros((max_rows, max(n_cont, 1)), np.float32)
    has_labels = with_labels and encoder.class_field is not None and \
        bool(encoder.class_values)
    labels = np.zeros(max_rows, np.int32) if has_labels else None
    has_ids = encoder.id_field is not None
    id_off = np.zeros(max_rows, np.int64) if has_ids else None
    id_len = np.zeros(max_rows, np.int32) if has_ids else None
    err_row = ctypes.c_long(0)
    if nthreads is None:
        nthreads = min(os.cpu_count() or 1, 8)
    rows = lib.avenir_csv_encode_mt(
        data, len(data), ctypes.c_char(delim.encode()), ncols,
        kinds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ordinals.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        widths.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        nbins.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(kinds), vocab_blob,
        codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        max(n_binned, 1),
        cont.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        max(n_cont, 1),
        (labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
         if labels is not None else None),
        (id_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
         if id_off is not None else None),
        (id_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
         if id_len is not None else None),
        max_rows, ctypes.byref(err_row), nthreads)
    if rows < 0:
        raise ValueError(
            f"{_ERRORS.get(rows, 'parse error')} at row {err_row.value}")
    ids = None
    if has_ids and rows:
        # id extraction: native gather of the id byte ranges, widened to
        # UCS4, directly into U-dtype memory (null-padded; numpy drops
        # trailing nulls). One pass, no numpy temporaries, no astype — the
        # numpy gather + astype('U') pair this replaces dominated encode
        # time. U-dtype (not object): no per-row PyObject creation;
        # elements compare equal to str.
        off = id_off[:rows]
        ln = id_len[:rows]
        maxlen = max(int(ln.max()), 1)
        chars = np.empty((rows, maxlen), np.uint32)  # gather fills every slot
        ascii_ok = lib.avenir_gather_ids_u32(
            data, off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            ln.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            rows, chars.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            maxlen)
        if ascii_ok:
            ids = chars.view(f"<U{maxlen}")[:, 0]
        else:                            # non-ASCII ids: slow exact path
            ids = np.array([data[off[i]:off[i] + ln[i]].decode()
                            for i in range(rows)], dtype=object)
    return EncodedDataset(
        codes=codes[:rows, :n_binned] if n_binned else np.zeros((rows, 0), np.int32),
        cont=cont[:rows, :n_cont] if n_cont else np.zeros((rows, 0), np.float32),
        labels=labels[:rows] if labels is not None else None,
        ids=ids,
        n_bins=np.array([encoder.n_bins[f.ordinal] for f in encoder.binned_fields],
                        np.int32),
        class_values=list(encoder.class_values),
        binned_ordinals=[f.ordinal for f in encoder.binned_fields],
        cont_ordinals=[f.ordinal for f in encoder.cont_fields],
    )


def iter_encoded_native(path: str, encoder, ncols: int, delim: str = ",",
                        chunk_bytes: int = 64 << 20, with_labels: bool = True):
    """Stream a CSV file through the native encoder in newline-aligned byte
    chunks — the TPU infeed producer."""
    with open(path, "rb") as fh:
        carry = b""
        while True:
            block = fh.read(chunk_bytes)
            if not block:
                if carry.strip():
                    yield encode_bytes(carry, encoder, ncols, delim, with_labels)
                return
            block = carry + block
            cut = block.rfind(b"\n")
            if cut < 0:
                carry = block
                continue
            carry = block[cut + 1:]
            yield encode_bytes(block[:cut + 1], encoder, ncols, delim, with_labels)
