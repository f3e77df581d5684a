"""Loader for the native C++ scan library.

Compiles ``native/pio_scan.cpp`` with g++ on first use (cached in the
PIO_FS_BASEDIR), loads it via ctypes, and exposes ``scan_jsonl_columnar``.
Everything degrades gracefully: no compiler / failed build -> ``None`` and
callers use the pure-Python path.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_lib_failed = False


def _source_path() -> str:
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(repo_root, "native", "pio_scan.cpp")


def _build_dir() -> str:
    base = os.environ.get(
        "PIO_FS_BASEDIR", os.path.join(os.path.expanduser("~"), ".pio_store")
    )
    d = os.path.join(base, "native")
    os.makedirs(d, exist_ok=True)
    return d


def _compiler_version() -> bytes:
    """`g++ --version` first line; a compiler upgrade must invalidate the
    cached .so exactly like a source edit does (ABI/codegen changes)."""
    try:
        out = subprocess.run(
            ["g++", "--version"], capture_output=True, timeout=15
        ).stdout
        return out.splitlines()[0] if out else b"unknown"
    except (subprocess.SubprocessError, OSError, IndexError):
        return b"unknown"


def get_library() -> ctypes.CDLL | None:
    global _lib, _lib_failed
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        src = _source_path()
        if not os.path.exists(src):
            _lib_failed = True
            return None
        # cache key = source bytes + compiler identity: a stale .so must
        # never be loaded after pio_scan.cpp OR the toolchain changes
        h = hashlib.sha256()
        with open(src, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
        h.update(_compiler_version())
        digest = h.hexdigest()[:16]
        so_path = os.path.join(_build_dir(), f"pio_scan_{digest}.so")
        if not os.path.exists(so_path):
            # per-process tmp name: multi-host workers share PIO_FS_BASEDIR
            # and compile concurrently — a shared ".tmp" let one process
            # install another's half-written ELF under the digest name
            tmp = f"{so_path}.{os.getpid()}.tmp"
            try:
                subprocess.run(
                    [
                        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                        "-o", tmp, src,
                    ],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(tmp, so_path)
                logger.info("built native scan library: %s", so_path)
            except (subprocess.SubprocessError, OSError) as exc:
                logger.warning("native build failed (%s); using python path", exc)
                _lib_failed = True
                return None
        try:
            lib = ctypes.CDLL(so_path)
        except OSError as exc:
            logger.warning("cannot load %s: %s", so_path, exc)
            _lib_failed = True
            return None
        lib.pio_scan_file.restype = ctypes.c_void_p
        lib.pio_scan_file.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p,
        ]
        lib.pio_scan_num_rows.restype = ctypes.c_int64
        lib.pio_scan_num_rows.argtypes = [ctypes.c_void_p]
        lib.pio_scan_error.restype = ctypes.c_char_p
        lib.pio_scan_error.argtypes = [ctypes.c_void_p]
        lib.pio_scan_copy_int32.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int32)]
        lib.pio_scan_copy_f64.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_double)]
        lib.pio_scan_copy_f32.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float)]
        lib.pio_scan_vocab_size.restype = ctypes.c_int64
        lib.pio_scan_vocab_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pio_scan_vocab_get.restype = ctypes.c_char_p
        lib.pio_scan_vocab_get.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_int64]
        lib.pio_scan_row_id.restype = ctypes.c_char_p
        lib.pio_scan_row_id.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pio_scan_ids_total_bytes.restype = ctypes.c_int64
        lib.pio_scan_ids_total_bytes.argtypes = [ctypes.c_void_p]
        lib.pio_scan_copy_ids.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_char_p,
        ]
        lib.pio_scan_free.argtypes = [ctypes.c_void_p]
        lib.pio_coo_group.restype = ctypes.c_int32
        lib.pio_coo_group.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.pio_degrees.restype = ctypes.c_int32
        lib.pio_degrees.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.pio_cooccur_topn.restype = ctypes.c_int32
        lib.pio_cooccur_topn.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
            ctypes.c_int32,
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32),
        ]
        _lib = lib
        return _lib


def cooccur_topn(
    users: np.ndarray, items: np.ndarray, n_items: int, top_n: int
) -> tuple[np.ndarray, np.ndarray] | None:
    """Dense-row cooccurrence count + top-N select at C++ speed. ``users``
    must be sorted ascending with DISTINCT (user, item) pairs (the shape
    ``np.unique`` over 1-D codes produces). Returns ``(items, counts)``
    matrices of shape (n_items, top_n), item slots padded with -1 — or
    None when the native library is unavailable or declines (huge vocab,
    out-of-range ids), in which case callers use the scipy path."""
    lib = get_library()
    if lib is None:
        return None
    users = np.ascontiguousarray(users, np.int32)
    items = np.ascontiguousarray(items, np.int32)
    out_items = np.empty((n_items, top_n), np.int32)
    out_counts = np.empty((n_items, top_n), np.int32)
    rc = lib.pio_cooccur_topn(
        users.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        items.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        users.shape[0],
        n_items,
        top_n,
        out_items.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc != 0:
        return None
    return out_items, out_counts


def coo_group(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, n_entities: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """Stable group-by-entity of a COO rating list at C++ speed: returns
    ``(cols_sorted, vals_sorted, deg)`` where rows are grouped by ascending
    entity id (original order preserved within an entity) and ``deg`` is the
    per-entity rating count. Returns None when the native library is
    unavailable (callers fall back to numpy argsort)."""
    lib = get_library()
    if lib is None:
        return None
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    vals = np.ascontiguousarray(vals, np.float32)
    n = rows.shape[0]
    cols_out = np.empty(n, np.int32)
    vals_out = np.empty(n, np.float32)
    deg = np.zeros(n_entities, np.int32)
    rc = lib.pio_coo_group(
        rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        cols.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n,
        n_entities,
        cols_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals_out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        deg.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc != 0:
        return None
    return cols_out, vals_out, deg


def degrees(ids: np.ndarray, n_entities: int) -> np.ndarray | None:
    """Per-entity counts (int32 ``[n_entities]``) of an id column, every id
    checked against ``[0, n_entities)`` in the same pass. Returns None when
    the native library is unavailable or an id is out of range (callers find
    out which with numpy, and count with ``np.bincount``)."""
    lib = get_library()
    if lib is None:
        return None
    ids = np.ascontiguousarray(ids, np.int32)
    deg = np.zeros(n_entities, np.int32)
    rc = lib.pio_degrees(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ids.shape[0],
        n_entities,
        deg.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return deg if rc == 0 else None


def scan_jsonl_columnar(
    path: str,
    event_names: list[str] | None = None,
    rating_key: str = "rating",
    entity_type: str | None = None,
    target_entity_type: str | None = None,
):
    """Native columnar scan of a JSONL event file. Returns a dict of numpy
    columns + vocab lists, or None when the native path is unavailable."""
    lib = get_library()
    if lib is None or not os.path.exists(path):
        return None
    csv = ",".join(event_names) if event_names else ""
    handle = lib.pio_scan_file(
        path.encode(),
        csv.encode(),
        rating_key.encode(),
        (entity_type or "").encode(),
        (target_entity_type or "").encode(),
    )
    try:
        err = lib.pio_scan_error(handle)
        if err:
            logger.warning("native scan error: %s", err.decode())
            return None
        n = lib.pio_scan_num_rows(handle)
        entity_ids = np.empty(n, np.int32)
        target_ids = np.empty(n, np.int32)
        event_codes = np.empty(n, np.int32)
        timestamps = np.empty(n, np.float64)
        ratings = np.empty(n, np.float32)
        if n:
            lib.pio_scan_copy_int32(
                handle, 0, entity_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            lib.pio_scan_copy_int32(
                handle, 1, target_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            lib.pio_scan_copy_int32(
                handle, 2, event_codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
            lib.pio_scan_copy_f64(
                handle, timestamps.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
            lib.pio_scan_copy_f32(
                handle, ratings.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))

        def vocab(which: int) -> list[str]:
            size = lib.pio_scan_vocab_size(handle, which)
            return [
                lib.pio_scan_vocab_get(handle, which, i).decode()
                for i in range(size)
            ]

        # row ids in TWO ffi calls (lengths + one concatenated buffer):
        # a pio_scan_row_id call + decode per row was a python loop that
        # rivaled the whole C++ scan at 20M rows
        event_ids: list[str] = []
        if n:
            lengths = np.empty(n, np.int32)
            buf = ctypes.create_string_buffer(
                max(1, int(lib.pio_scan_ids_total_bytes(handle)))
            )
            lib.pio_scan_copy_ids(
                handle,
                lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                buf,
            )
            raw = buf.raw
            pos = 0
            for ln in lengths.tolist():
                event_ids.append(raw[pos : pos + ln].decode())
                pos += ln

        return {
            "entity_ids": entity_ids,
            "target_ids": target_ids,
            "event_codes": event_codes,
            "timestamps": timestamps,
            "ratings": ratings,
            "entity_vocab": vocab(0),
            "target_vocab": vocab(1),
            "event_vocab": vocab(2),
            "event_ids": event_ids,
        }
    finally:
        lib.pio_scan_free(handle)
