"""Native runtime components (C++, ctypes-bound).

The compute path is JAX/XLA on the TPU; the *runtime around it* — here the
checkpoint row codec — is native C++ where the reference's equivalent tier
is native Rust (src/common/src/util/value_encoding/, memcmp_encoding.rs).
The library builds on first use with the in-image toolchain (g++ -O3) and
caches the .so next to the source keyed by a content hash; environments
without a compiler fall back to the Python encoders transparently
(``codec() is None``). Set RW_TPU_DISABLE_NATIVE=1 to force the fallback.
"""

from __future__ import annotations

import ctypes
import hashlib
import operator
import os
import subprocess
from itertools import compress, repeat
from typing import Optional, Sequence

import numpy as np

from ..common.packed import PackedBatch, PackedColumn

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "rowcodec.cpp")

_lib = None
_tried = False

_LL_P = ctypes.POINTER(ctypes.c_longlong)
_UB_P = ctypes.POINTER(ctypes.c_ubyte)

# DataType.kind -> native type code (rowcodec.cpp header comment)
_CODE_BY_KIND = {
    "BOOL": 0, "INT16": 1, "INT32": 2, "DATE": 2,
    "INT64": 3, "TIME": 3, "TIMESTAMP": 3, "INTERVAL": 3, "SERIAL": 3,
    "DECIMAL": 3,
    "FLOAT32": 4, "FLOAT64": 5,
    "VARCHAR": 6, "BYTEA": 6,
}


def _build() -> Optional[ctypes.CDLL]:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_DIR, f"_rowcodec_{digest}.so")
    if not os.path.exists(so_path):
        tmp = so_path + f".tmp{os.getpid()}"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
               _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so_path)
        except (OSError, subprocess.SubprocessError):
            return None
    lib = ctypes.CDLL(so_path)
    lib.rw_encode.restype = ctypes.c_longlong
    lib.rw_encode.argtypes = [
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_longlong),
        ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_ubyte),
        ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_longlong),
    ]
    lib.rw_encode_segment_table.restype = ctypes.c_longlong
    lib.rw_encode_segment_table.argtypes = [
        ctypes.c_char_p, _LL_P, ctypes.c_char_p, _LL_P,
        _UB_P, ctypes.c_longlong, _UB_P, ctypes.c_longlong, _LL_P,
    ]
    if lib.rw_abi_version() != 3:
        return None
    return lib


import threading as _threading

_build_lock = _threading.Lock()


def codec() -> Optional["RowCodec"]:
    """The process-wide codec, or None when native is unavailable.
    Thread-safe: sessions pre-warm the build from a background thread."""
    global _lib, _tried
    with _build_lock:
        if not _tried:
            _tried = True
            if os.environ.get("RW_TPU_DISABLE_NATIVE") != "1":
                lib = _build()
                if lib is not None:
                    _lib = RowCodec(lib)
    return _lib


class RowCodec:
    def __init__(self, lib: ctypes.CDLL):
        self.lib = lib

    @staticmethod
    def supports(types) -> bool:
        """True iff the codec has code for every one of ``types`` (LIST,
        STRUCT and JSONB rows stay with the Python encoders)."""
        return all(t.kind.name in _CODE_BY_KIND for t in types)

    def _prep_columns(self, datas: Sequence[np.ndarray],
                      masks: Sequence[np.ndarray], types) -> tuple:
        """-> (codes, data_ptrs, mask_ptrs, blob_ptrs, off_ptrs, keepalive,
        blob_bytes)"""
        from ..common.types import GLOBAL_STRING_DICT
        n = len(types)
        codes = (ctypes.c_int * n)()
        data_ptrs = (ctypes.c_void_p * n)()
        mask_ptrs = (ctypes.c_void_p * n)()
        blob_ptrs = (ctypes.c_void_p * n)()
        off_ptrs = (ctypes.c_void_p * n)()
        keep = []
        blob_bytes = 0
        for i, t in enumerate(types):
            code = _CODE_BY_KIND[t.kind.name]
            codes[i] = code
            mask = np.ascontiguousarray(masks[i], np.uint8)
            keep.append(mask)
            mask_ptrs[i] = mask.ctypes.data_as(ctypes.c_void_p).value
            if code == 6:
                # datas[i] is already delta-gathered by _encode: the uniq
                # set and blob are dirty-sized, not capacity-sized
                ids = np.ascontiguousarray(datas[i]).astype(np.int64)
                uniq, inv = np.unique(ids, return_inverse=True)
                parts = [GLOBAL_STRING_DICT.lookup(int(u)).encode("utf-8")
                         for u in uniq]
                offs = np.zeros(len(parts) + 1, np.int64)
                np.cumsum([len(p) for p in parts], out=offs[1:])
                blob = np.frombuffer(b"".join(parts) or b"\x00", np.uint8)
                blob_bytes += max((len(p) for p in parts), default=0)
                inv64 = np.ascontiguousarray(inv, np.int64)
                keep.extend((blob, offs, inv64))
                data_ptrs[i] = inv64.ctypes.data_as(ctypes.c_void_p).value
                blob_ptrs[i] = blob.ctypes.data_as(ctypes.c_void_p).value
                off_ptrs[i] = offs.ctypes.data_as(ctypes.c_void_p).value
            else:
                # coerce to the dtype the C side reads for this code —
                # the Python encoders coerce via int()/float() the same way
                want = {0: np.uint8, 1: np.int16, 2: np.int32,
                        3: np.int64, 4: np.float32, 5: np.float64}[code]
                arr = np.ascontiguousarray(datas[i])
                if arr.dtype != want:
                    arr = arr.astype(want)
                keep.append(arr)
                data_ptrs[i] = arr.ctypes.data_as(ctypes.c_void_p).value
        return codes, data_ptrs, mask_ptrs, blob_ptrs, off_ptrs, keep, \
            blob_bytes

    def _encode(self, key_mode: int, datas, masks, types,
                indices: np.ndarray) -> PackedColumn:
        n = len(types)
        sel = np.ascontiguousarray(indices, np.int64)
        n_sel = len(sel)
        if n_sel == 0:
            return PackedColumn.empty()
        # gather the dirty delta FIRST: all per-column prep (string
        # uniquing, dtype coercion) must scale with the delta, not the
        # full state capacity
        datas = [np.asarray(d).reshape(-1)[sel] for d in datas]
        masks = [np.asarray(m).reshape(-1)[sel] for m in masks]
        (codes, data_ptrs, mask_ptrs, blob_ptrs, off_ptrs, keep,
         blob_bytes) = self._prep_columns(datas, masks, types)
        idx = np.arange(n_sel, dtype=np.int64)
        out_offsets = np.zeros(n_sel + 1, np.int64)
        # capacity estimate: ≤9B per fixed col per row; each string col
        # ≤ 2x its longest string (escape doubling) + framing per row
        cap = n_sel * (9 * n + 8 + 2 * blob_bytes + 6) + 64
        for _ in range(3):
            out = np.empty(cap, np.uint8)
            written = self.lib.rw_encode(
                key_mode, n, codes, data_ptrs, mask_ptrs, blob_ptrs,
                off_ptrs, idx.ctypes.data_as(_LL_P), n_sel,
                out.ctypes.data_as(_UB_P), cap,
                out_offsets.ctypes.data_as(_LL_P))
            if written >= 0:
                return PackedColumn(out[:written].tobytes(), out_offsets)
            cap *= 4
        raise RuntimeError("native row encode: buffer growth failed")

    def pack_value_rows(self, datas, masks, types, indices) -> PackedColumn:
        """Columnar buffers -> the selected rows value-encoded
        (byte-identical to common/row.py encode_value_row), packed."""
        return self._encode(0, datas, masks, types, indices)

    def pack_keys(self, datas, masks, types, indices) -> PackedColumn:
        """Columnar buffers -> the selected rows' memcomparable keys
        (byte-identical to common/row.py encode_key), packed."""
        return self._encode(1, datas, masks, types, indices)

    def encode_value_rows(self, datas, masks, types, indices) -> list:
        """``pack_value_rows`` cut into one ``bytes`` a row."""
        return self.pack_value_rows(datas, masks, types, indices).cut()

    def encode_keys(self, datas, masks, types, indices) -> list:
        """``pack_keys`` cut into one ``bytes`` a row."""
        return self.pack_keys(datas, masks, types, indices).cut()

    @staticmethod
    def _unpack_dict(buf: dict) -> tuple:
        """A dict layer ``{key: value | None}`` -> ``(key_blob, key_lens,
        val_blob, val_lens, live)``, the values those of the live rows
        alone, with C-speed calls only."""
        n = len(buf)
        keys = list(buf)
        vals = list(buf.values())
        klens = np.fromiter(map(len, keys), np.int64, count=n)
        n_live = n - vals.count(None)
        if n_live == n:
            live = np.ones(n, np.uint8)
        else:
            live = np.fromiter(map(operator.is_not, vals, repeat(None)),
                               np.uint8, count=n)
            vals = list(compress(vals, live.tolist()))
        vlens = np.fromiter(map(len, vals), np.int64, count=n_live)
        return b"".join(keys), klens, b"".join(vals), vlens, live

    def encode_segment_table(self, layers: list) -> Optional[tuple]:
        """One table's delta layers (common/packed.py: packed batches and
        dicts ``{key: value | None}``, in application order) -> ``(block,
        rows)``: its rows as a checkpoint segment lays them out and how
        many there are (byte-identical to the row loop of
        storage/checkpoint.py ``_encode_segment_py`` over the layers' dict
        view: ordered by key, the last row of a key alone, ``<H klen> key
        0x00`` for a tombstone, ``<H klen> key 0x01 <I vlen> value`` for a
        put), or None where a length does not fit the layout. A packed
        batch is handed on as it is and a dict is taken apart with C-speed
        calls only: nothing here runs once a row in Python."""
        parts = [
            (layer.keys.blob, layer.keys.lens(), layer.values.blob,
             layer.values.lens(), layer.live)
            if isinstance(layer, PackedBatch) else self._unpack_dict(layer)
            for layer in layers if len(layer)]
        if not parts:
            return np.empty(0, np.uint8), 0
        if len(parts) == 1:
            key_blob, klens, val_blob, vlens, live = parts[0]
        else:
            key_blobs, klens, val_blobs, vlens, live = zip(*parts)
            key_blob, val_blob = b"".join(key_blobs), b"".join(val_blobs)
            klens, vlens, live = (np.concatenate(klens),
                                  np.concatenate(vlens),
                                  np.concatenate(live))
        if klens.max() > 0xFFFF or vlens.max(initial=0) > 0xFFFFFFFF:
            return None
        n = len(live)
        # room for every row; the rows a later one shadows are not written
        size = 3 * n + len(key_blob) + 4 * len(vlens) + len(val_blob)
        out = np.empty(size, np.uint8)
        rows = ctypes.c_longlong(0)
        written = self.lib.rw_encode_segment_table(
            key_blob, klens.ctypes.data_as(_LL_P),
            val_blob, vlens.ctypes.data_as(_LL_P),
            live.ctypes.data_as(_UB_P), n,
            out.ctypes.data_as(_UB_P), size, ctypes.byref(rows))
        if written < 0 or written > size:
            raise RuntimeError(
                f"native segment encode: wrote {written} of {size} bytes")
        return out[:written], rows.value
