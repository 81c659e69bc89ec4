"""ctypes bridge to the C++ data plane (runtime/native/csv_encode.cpp).

Compiles the shared library on first use (g++, kept next to the source,
never committed; rebuilt when the source is newer) and exposes :func:`encode_bytes` — CSV
bytes → :class:`EncodedDataset` with semantics identical to
``DatasetEncoder.transform`` — and :class:`EncoderSpecs`, the same kernel
with an encoder's specs built once for many calls. All callers must treat
this as an optional fast path: :func:`is_available` gates it, and
``DatasetEncoder`` stays the portable reference implementation.
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
_encode = None                         # avenir_csv_encode_mt, see _get_lib
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
    global _lib, _encode
    with _lock:
        if _lib is None and _build_error is None:
            lib = _build()
            if lib is not None:
                i32p = ctypes.POINTER(ctypes.c_int32)
                vp = ctypes.c_void_p
                # a handle of its own (``lib[...]`` is not cached on the
                # library), whose array arguments are plain addresses: a
                # call converts ints, not numpy arrays into ctypes pointers
                _encode = lib["avenir_csv_encode_mt"]
                _encode.restype = ctypes.c_long
                _encode.argtypes = [
                    ctypes.c_char_p, ctypes.c_long, ctypes.c_char,
                    ctypes.c_int32,
                    vp, vp, vp, vp, vp, ctypes.c_int32, ctypes.c_char_p,
                    vp, ctypes.c_long, vp, ctypes.c_long,
                    vp, vp, vp,
                    ctypes.c_long, ctypes.POINTER(ctypes.c_long),
                    ctypes.c_int32,
                ]
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


def _specs_from_encoder(encoder, with_labels: bool = True,
                        with_ids: bool = True) -> tuple:
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
    if with_ids and encoder.id_field is not None:
        kinds.append(KIND_ID)
        ordinals.append(encoder.id_field.ordinal)
        widths.append(0.0)
        offsets.append(0)
        nbins.append(0)
    return (np.asarray(kinds, np.int32), np.asarray(ordinals, np.int32),
            np.asarray(widths, np.float64), np.asarray(offsets, np.int64),
            np.asarray(nbins, np.int32), b"".join(vocab_parts))


class EncoderSpecs:
    """A fitted encoder flattened ONCE into the kernel's spec arrays, their
    addresses taken once too, so that a call pays for the parse and little
    else: what a caller that encodes many small batches under one encoder
    (a servable) builds at construction.  Read-only after construction, so
    threads may share one.  ``with_ids=False`` leaves the id column
    unparsed (``ids`` is then None); ``encoder`` is read by attribute only.
    Raises RuntimeError if the native library is unavailable."""

    def __init__(self, encoder, with_labels: bool = True,
                 with_ids: bool = True):
        if _get_lib() is None:
            raise RuntimeError(f"native library unavailable: {_build_error}")
        kinds, ordinals, widths, offsets, nbins, self._vocab = \
            _specs_from_encoder(encoder, with_labels=with_labels,
                                with_ids=with_ids)
        self._arrays = (kinds, ordinals, widths, offsets, nbins)  # kept alive
        self._spec_args = tuple(a.ctypes.data for a in self._arrays) + \
            (len(kinds), self._vocab)
        self.n_binned = len(encoder.binned_fields)
        self.n_cont = len(encoder.cont_fields)
        self.has_labels = with_labels and encoder.class_field is not None \
            and bool(encoder.class_values)
        self.has_ids = with_ids and encoder.id_field is not None
        self.n_bins = np.array(
            [encoder.n_bins[f.ordinal] for f in encoder.binned_fields],
            np.int32)
        self.class_values = list(encoder.class_values)
        self.binned_ordinals = [f.ordinal for f in encoder.binned_fields]
        self.cont_ordinals = [f.ordinal for f in encoder.cont_fields]

    def encode(self, data: bytes, ncols: int, delim: str = ",",
               rows: Optional[int] = None, pad_to: int = 0,
               nthreads: int = 1):
        """CSV bytes → EncodedDataset.  ``rows``: the number of records
        ``data`` must hold (None: whatever it holds).  ``pad_to``: the
        arrays hold at least that many rows, those past the data zero, as
        ``serving/registry.py::_pad_ds`` pads.  Raises ValueError on a data
        error or a record count other than ``rows``."""
        from avenir_tpu.core.encoding import EncodedDataset

        max_rows = _lib.avenir_csv_count_rows(data, len(data)) \
            if rows is None else rows
        size = max(max_rows, pad_to)
        nb, nc = max(self.n_binned, 1), max(self.n_cont, 1)
        codes = np.zeros((size, nb), np.int32)
        cont = np.zeros((size, nc), np.float32)
        labels = np.zeros(max_rows, np.int32) if self.has_labels else None
        id_off = np.zeros(max_rows, np.int64) if self.has_ids else None
        id_len = np.zeros(max_rows, np.int32) if self.has_ids else None
        err_row = ctypes.c_long(0)
        got = _encode(
            data, len(data), delim.encode(), ncols, *self._spec_args,
            codes.ctypes.data, nb, cont.ctypes.data, nc,
            None if labels is None else labels.ctypes.data,
            None if id_off is None else id_off.ctypes.data,
            None if id_len is None else id_len.ctypes.data,
            max_rows, ctypes.byref(err_row), nthreads)
        if got < 0:
            raise ValueError(
                f"{_ERRORS.get(got, 'parse error')} at row {err_row.value}")
        if rows is not None and got != rows:
            raise ValueError(f"{got} records where {rows} were expected")
        ids = _gather_ids(data, id_off[:got], id_len[:got]) \
            if self.has_ids and got else None
        keep = max(got, pad_to)
        return EncodedDataset(
            codes=codes[:keep, :self.n_binned],
            cont=cont[:keep, :self.n_cont],
            labels=labels[:got] if labels is not None else None,
            ids=ids, n_bins=self.n_bins.copy(),
            class_values=list(self.class_values),
            binned_ordinals=list(self.binned_ordinals),
            cont_ordinals=list(self.cont_ordinals),
            valid_rows=got if keep > got else None)


def _gather_ids(data: bytes, off: np.ndarray, ln: np.ndarray) -> np.ndarray:
    """The id fields at (offset, length) of ``data``.  A native gather of
    the byte ranges, widened to UCS4, directly into U-dtype memory
    (null-padded; numpy drops trailing nulls): one pass, no numpy
    temporaries, no astype — the numpy gather + astype('U') pair this
    replaces dominated encode time.  U-dtype (not object): no per-row
    PyObject creation; elements compare equal to str."""
    rows = len(off)
    maxlen = max(int(ln.max()), 1)
    chars = np.empty((rows, maxlen), np.uint32)  # gather fills every slot
    ascii_ok = _lib.avenir_gather_ids_u32(
        data, off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ln.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        rows, chars.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), maxlen)
    if ascii_ok:
        return chars.view(f"<U{maxlen}")[:, 0]
    return np.array([data[off[i]:off[i] + ln[i]].decode()   # non-ASCII ids:
                     for i in range(rows)], dtype=object)  # slow exact path


def encode_bytes(data: bytes, encoder, ncols: int, delim: str = ",",
                 with_labels: bool = True, nthreads: Optional[int] = None):
    """CSV bytes → EncodedDataset via the native kernel.

    ``encoder`` must be a fitted DatasetEncoder; raises ValueError on data
    errors (same conditions as the Python path) and RuntimeError if the
    native library is unavailable. Buffers over 1 MiB are parsed by
    ``nthreads`` worker threads (default: up to 8 or the CPU count) with
    output identical to the single-threaded path.
    """
    if nthreads is None:
        nthreads = min(os.cpu_count() or 1, 8)
    return EncoderSpecs(encoder, with_labels=with_labels).encode(
        data, ncols, delim, nthreads=nthreads)


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
