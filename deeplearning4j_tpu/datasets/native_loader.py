"""ctypes bindings for the native C++ data-loading runtime
(native/dataloader.cpp): CSV/IDX record readers with a background prefetch
ring — the native analog of the reference's DataVec record readers +
AsyncDataSetIterator (SURVEY.md §2.3, §2.9). Auto-builds with make on first
use if the shared library is missing (it is never committed: ``*.so`` is
git-ignored); falls back to the pure-Python iterators, with a logged
warning, when the build fails."""

from __future__ import annotations

import ctypes
import logging
import subprocess
from pathlib import Path
from typing import Optional

import numpy as np

from ..ops.dataset import DataSet
from .iterators import DataSetIterator

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libdl4jtpu_native.so"
_lib = None


def _load_lib():
    global _lib
    if _lib is not None:
        return _lib
    if not _LIB_PATH.exists():
        try:
            subprocess.run(["make", "-C", str(_NATIVE_DIR)], check=True,
                           capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as e:
            # no toolchain / failed build: the Python path serves, and the
            # log says why the native one does not
            logging.getLogger(__name__).warning(
                "native build failed (make -C %s): %s %s", _NATIVE_DIR, e,
                (getattr(e, "stderr", None) or b"")[-400:])
            return None
    if not _LIB_PATH.exists():
        return None
    lib = ctypes.CDLL(str(_LIB_PATH))
    lib.csv_loader_create.restype = ctypes.c_void_p
    lib.csv_loader_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_uint64, ctypes.c_int, ctypes.c_char]
    lib.idx_loader_create.restype = ctypes.c_void_p
    lib.idx_loader_create.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
        ctypes.c_uint64]
    for fn in ("loader_num_examples", "loader_feature_cols",
               "loader_label_cols", "loader_next"):
        getattr(lib, fn).restype = ctypes.c_int64
    lib.loader_num_examples.argtypes = [ctypes.c_void_p]
    lib.loader_feature_cols.argtypes = [ctypes.c_void_p]
    lib.loader_label_cols.argtypes = [ctypes.c_void_p]
    lib.loader_next.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_float),
                                ctypes.POINTER(ctypes.c_float)]
    lib.loader_reset.argtypes = [ctypes.c_void_p]
    lib.loader_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def native_available() -> bool:
    return _load_lib() is not None


class _NativeIteratorBase(DataSetIterator):
    async_supported = False   # prefetch happens in the native ring already

    def __init__(self, handle, batch_size: int):
        self._h = handle
        self._bs = int(batch_size)
        lib = _load_lib()
        self._fc = lib.loader_feature_cols(self._h)
        self._lc = lib.loader_label_cols(self._h)
        self._n = lib.loader_num_examples(self._h)

    def __iter__(self):
        lib = _load_lib()
        fbuf = np.empty((self._bs, self._fc), np.float32)
        lbuf = np.empty((self._bs, max(self._lc, 1)), np.float32)
        while True:
            n = lib.loader_next(
                self._h, fbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                lbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
            if n == 0:
                lib.loader_reset(self._h)   # rearm for the next epoch
                return
            yield DataSet(fbuf[:n].copy(),
                          lbuf[:n].copy() if self._lc else None)

    def batch_size(self) -> int:
        return self._bs

    def total_examples(self) -> int:
        return int(self._n)

    def __del__(self):
        lib = _load_lib()
        if lib is not None and getattr(self, "_h", None):
            lib.loader_destroy(self._h)
            self._h = None


class NativeCSVDataSetIterator(_NativeIteratorBase):
    """CSV → DataSet batches via the native reader (reference
    RecordReaderDataSetIterator over CSVRecordReader)."""

    def __init__(self, path, batch_size: int, label_index: int = -1,
                 num_classes: int = 0, shuffle: bool = True, seed: int = 0,
                 skip_lines: int = 0, delimiter: str = ","):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native loader unavailable (no toolchain)")
        h = lib.csv_loader_create(str(path).encode(), batch_size,
                                  label_index, num_classes,
                                  1 if shuffle else 0, seed, skip_lines,
                                  delimiter.encode()[0])
        if not h:
            raise IOError(f"cannot load CSV {path}")
        super().__init__(h, batch_size)


class NativeMnistDataSetIterator(_NativeIteratorBase):
    """IDX files → DataSet batches via the native reader."""

    def __init__(self, images_path, labels_path, batch_size: int,
                 shuffle: bool = True, seed: int = 0):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native loader unavailable (no toolchain)")
        h = lib.idx_loader_create(str(images_path).encode(),
                                  str(labels_path).encode(), batch_size,
                                  1 if shuffle else 0, seed)
        if not h:
            raise IOError(f"cannot load IDX {images_path}")
        super().__init__(h, batch_size)
