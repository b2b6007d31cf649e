"""LibSVM -> CSR parsing with a native C++ fast path.

The parser (photon_ml_tpu/native/libsvm_loader.cpp) replaces the reference's
JVM-side LibSVM ingestion (photon-client io/deprecated/
LibSVMInputDataFormat.scala) with a single-pass C++ tokenizer; this module
exports it as numpy CSR arrays and falls back to a pure-Python parse when no
compiler is available. Semantic conventions (1-based indices by default,
±1 labels mapped to {0,1} for binary tasks) match io/data_reader.read_libsvm.
"""

from __future__ import annotations

import ctypes
import dataclasses
import logging
import os

import numpy as np

from photon_ml_tpu.native.build import libsvm_native_available, load_libsvm_library

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class LibSVMData:
    """CSR view of one or more LibSVM files.

    labels:      [n] float64, raw file labels
    row_offsets: [n+1] uint64
    cols:        [nnz] uint32 feature indices (0-based)
    vals:        [nnz] float64
    """

    labels: np.ndarray
    row_offsets: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    #: False when any part came through the Python tokenizer
    native: bool = True

    @property
    def num_rows(self) -> int:
        return int(self.labels.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.cols.shape[0])

    @property
    def max_index(self) -> int:
        """Largest 0-based feature index, -1 when no features at all."""
        return int(self.cols.max()) if self.nnz else -1

    def mapped_labels(self) -> np.ndarray:
        """±1 binary convention -> {0,1}; other values pass through
        (same rule as data_reader.read_libsvm)."""
        binary = np.isin(self.labels, (-1.0, 1.0))
        return np.where(binary, (self.labels > 0).astype(np.float64), self.labels)

    def to_dense(self, num_cols: int | None = None, dtype=np.float64) -> np.ndarray:
        """[n, d] dense matrix (duplicate idx:val tokens accumulate)."""
        d = (self.max_index + 1) if num_cols is None else num_cols
        x = np.zeros((self.num_rows, d), dtype=dtype)
        row_idx = np.repeat(
            np.arange(self.num_rows, dtype=np.intp),
            np.diff(self.row_offsets).astype(np.intp),
        )
        keep = self.cols < d
        np.add.at(
            x,
            (row_idx[keep], self.cols[keep].astype(np.intp)),
            self.vals[keep].astype(dtype),
        )
        return x


def _parse_native(path: str, zero_based: bool) -> LibSVMData:
    lib = load_libsvm_library()
    err = ctypes.create_string_buffer(512)
    handle = lib.lsvm_parse(
        os.fsencode(path), int(zero_based), err, ctypes.c_uint64(len(err))
    )
    if not handle:
        raise ValueError(f"libsvm parse failed: {err.value.decode()}")
    try:
        n = lib.lsvm_num_rows(handle)
        nnz = lib.lsvm_nnz(handle)
        labels = np.empty(n, dtype=np.float64)
        row_offsets = np.empty(n + 1, dtype=np.uint64)
        cols = np.empty(nnz, dtype=np.uint32)
        vals = np.empty(nnz, dtype=np.float64)
        lib.lsvm_export(
            handle,
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            row_offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            cols.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        )
        return LibSVMData(labels, row_offsets, cols, vals)
    finally:
        lib.lsvm_free(handle)


def _parse_python(path: str, zero_based: bool) -> LibSVMData:
    labels: list[float] = []
    offsets: list[int] = [0]
    cols: list[int] = []
    vals: list[float] = []
    with open(path, encoding="utf-8") as f:
        for line_no, line in enumerate(f, 1):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            labels.append(float(parts[0]))
            for tok in parts[1:]:
                if tok.startswith("#"):
                    break
                idx_s, sep, val_s = tok.partition(":")
                if not sep:
                    raise ValueError(
                        f"bad feature token {tok!r} at line {line_no} in {path}"
                    )
                idx = int(idx_s) - (0 if zero_based else 1)
                if idx < 0:
                    raise ValueError(
                        f"feature index out of range at line {line_no} in {path}"
                    )
                cols.append(idx)
                vals.append(float(val_s))
            offsets.append(len(cols))
    return LibSVMData(
        labels=np.asarray(labels, dtype=np.float64),
        row_offsets=np.asarray(offsets, dtype=np.uint64),
        cols=np.asarray(cols, dtype=np.uint32),
        vals=np.asarray(vals, dtype=np.float64),
        native=False,
    )


def parse_libsvm(
    path: str | os.PathLike, *, zero_based: bool = False, force_python: bool = False
) -> LibSVMData:
    """Parse one LibSVM file to CSR (native C++ when available)."""
    path = str(path)
    if os.path.isdir(path):
        raise IsADirectoryError(f"expected a LibSVM file, got directory: {path}")
    if not force_python:
        if libsvm_native_available():
            return _parse_native(path, zero_based)
        logger.warning("native LibSVM tokenizer unavailable (no C++ compiler "
                       "or a failed build); using the slower Python parser")
    return _parse_python(path, zero_based)


def concat_libsvm(parts: list[LibSVMData]) -> LibSVMData:
    """Concatenate several parsed files into one CSR block (date-range
    multi-path reads)."""
    if len(parts) == 1:
        return parts[0]
    labels = np.concatenate([p.labels for p in parts])
    cols = np.concatenate([p.cols for p in parts])
    vals = np.concatenate([p.vals for p in parts])
    offsets = [np.asarray([0], dtype=np.uint64)]
    base = np.uint64(0)
    for p in parts:
        offsets.append(p.row_offsets[1:] + base)
        base = base + p.row_offsets[-1]
    return LibSVMData(labels, np.concatenate(offsets), cols, vals,
                      native=all(p.native for p in parts))
