"""pathway_tpu.native — the C++ host runtime.

Builds ``_native.cpp`` into a CPython extension on first import (g++ -O3;
cached next to the source, rebuilt when the source changes) and exposes the
hot host-side loops the reference implements in Rust:

* ``hash_object_column`` — canonical-serialize + XXH64 a whole value column
  (reference ``Key::for_values``, src/engine/value.rs:57)
* ``consolidate_pairs`` — (key, row-hash) delta grouping with diff summing
  (differential-dataflow consolidation)
* ``split_lines`` — newline tokenizer for line-based connectors
  (reference src/connectors/data_tokenize.rs)

There is ONE way to get the module: the self-build, named by the content
hash of ``_native.cpp``, so the binary a process loads always came from the
source that sits next to it (an installed copy ships the source and builds
the same way). If the toolchain is missing the Python/numpy paths are used,
``AVAILABLE`` is False and the reason is logged as a warning — a caller that
must not measure the Python paths (``chip_smoke.py``) checks ``AVAILABLE``.
"""

from __future__ import annotations

import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_native.cpp")

AVAILABLE = False
lib = None


def _build_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    suffix = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_DIR, f"_native-{digest}{suffix}")


def _compile(out_path: str) -> bool:
    include = sysconfig.get_paths()["include"]
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++20",
        f"-I{include}", _SRC, "-o", out_path,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as exc:
        logger.warning("native build unavailable: %s", exc)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed:\n%s", proc.stderr[-2000:])
        return False
    return True


def _load():
    global AVAILABLE, lib
    from pathway_tpu.internals.config import pathway_config

    if pathway_config.disable_native:
        return
    path = _build_path()
    if not os.path.exists(path):
        tmp = path + f".tmp{os.getpid()}"
        if not _compile(tmp):
            return
        os.replace(tmp, path)
    try:
        spec = importlib.util.spec_from_file_location("_native", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
    except Exception as exc:  # noqa: BLE001
        logger.warning("native load failed: %s", exc)
        return
    lib = mod
    AVAILABLE = True


_load()

if AVAILABLE:
    import numpy as np

    def hash_object_column_native(col) -> "np.ndarray | None":
        """Column hash via the C++ path; rows the native serializer can't
        handle (ndarray/Json/datetimes/bigints) fall back per-row in Python.
        Returns None when native is unavailable."""
        n = len(col)
        out = np.empty(n, dtype=np.uint64)
        fallback = lib.hash_object_column(col, memoryview(out.view(np.uint8)))
        if fallback:
            from pathway_tpu.engine import value as value_mod

            for i in fallback:
                out[i] = value_mod.hash_one(col[i])
        return out

    def consolidate_pairs_native(keys, rowh, diffs):
        """Returns (first_indices u64 array, summed_diffs i64 array)."""
        idx_b, diff_b = lib.consolidate_pairs(
            memoryview(np.ascontiguousarray(keys, dtype=np.uint64)),
            memoryview(np.ascontiguousarray(rowh, dtype=np.uint64)),
            memoryview(np.ascontiguousarray(diffs, dtype=np.int64)),
        )
        return (
            np.frombuffer(idx_b, dtype=np.uint64),
            np.frombuffer(diff_b, dtype=np.int64),
        )

    def split_lines_native(data: bytes):
        """(start, end) offsets per line as an (n, 2) uint64 array."""
        offs = np.frombuffer(lib.split_lines(data), dtype=np.uint64)
        return offs.reshape(-1, 2)

    def hash_tokenize_native(texts, max_length: int, reserved: int,
                             span: int):
        """Batch HashTokenizer ids as (writable (n, width) int32 matrix,
        fallback row indices needing Python re-tokenization — texts with
        non-ASCII bytes, where Unicode case folding applies), or None for
        inputs the C++ path rejects outright (non-strings)."""
        try:
            buf, width, fallback = lib.hash_tokenize(
                texts, max_length, reserved, span
            )
        except TypeError:
            return None
        ids = np.frombuffer(buf, dtype=np.int32).reshape(len(texts), width)
        return ids, fallback

    def wordpiece_load_native(tokens) -> int:
        """Register a WordPiece vocab (list of token strings, index = id);
        returns an opaque handle for wordpiece_tokenize_native."""
        return lib.wordpiece_load(list(tokens))

    def wordpiece_tokenize_native(handle: int, texts, max_length: int,
                                  cls_id: int, sep_id: int, unk_id: int,
                                  pad_id: int):
        """Batch WordPiece ids as (writable (n, width) int32 matrix,
        per-row real lengths, fallback row indices — non-ASCII texts
        needing the Python path), or None for inputs the C++ path rejects
        (non-strings)."""
        try:
            buf, width, lens_buf, fallback = lib.wordpiece_tokenize(
                handle, texts, max_length, cls_id, sep_id, unk_id, pad_id
            )
        except TypeError:
            return None
        ids = np.frombuffer(buf, dtype=np.int32).reshape(len(texts), width)
        lens = np.frombuffer(lens_buf, dtype=np.uint32)
        return ids, lens, fallback

else:
    hash_object_column_native = None  # type: ignore[assignment]
    consolidate_pairs_native = None  # type: ignore[assignment]
    split_lines_native = None  # type: ignore[assignment]
    hash_tokenize_native = None  # type: ignore[assignment]
    wordpiece_load_native = None  # type: ignore[assignment]
    wordpiece_tokenize_native = None  # type: ignore[assignment]
