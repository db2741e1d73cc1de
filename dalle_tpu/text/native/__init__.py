"""Lazy g++ build + ctypes binding for the native BPE core."""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import List, Optional

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "bpe_core.cpp"
_lock = threading.Lock()
_lib = None
_build_failed = False


def _lib_path() -> Path:
    """The binary's name carries its source's digest: a binary copied along
    with the tree (file times do not survive a copy) loads only if it was
    built from exactly the committed source, otherwise it is rebuilt."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _HERE / f"libbpe_core.{digest}.so"


def _load() -> Optional[ctypes.CDLL]:
    """Compile (once) and dlopen the core; returns None if no toolchain."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            lib_path = _lib_path()
            if not lib_path.exists():
                for stale in _HERE.glob("libbpe_core*.so"):
                    stale.unlink(missing_ok=True)
                # build beside the target and rename: concurrent processes
                # (xdist workers) never dlopen a half-written file
                tmp = lib_path.with_suffix(f".{os.getpid()}.tmp")
                subprocess.run(
                    ["g++", "-O2", "-shared", "-fPIC", str(_SRC), "-o", str(tmp)],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, lib_path)
            lib = ctypes.CDLL(str(lib_path))
            lib.bpe_new.restype = ctypes.c_void_p
            lib.bpe_new.argtypes = [ctypes.c_char_p]
            lib.bpe_free.argtypes = [ctypes.c_void_p]
            lib.bpe_num_merges.restype = ctypes.c_int32
            lib.bpe_num_merges.argtypes = [ctypes.c_void_p]
            lib.bpe_encode_word.restype = ctypes.c_int32
            lib.bpe_encode_word.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.c_char_p, ctypes.c_int32]
            _lib = lib
        except (subprocess.SubprocessError, OSError):
            _build_failed = True
        return _lib


class NativeBPE:
    """ctypes wrapper over the C++ merge engine. ``available()`` gates use so
    the pure-Python path transparently takes over without a toolchain."""

    SEP = "\x01"

    def __init__(self, merges: List[tuple]):
        lib = _load()
        if lib is None:
            raise RuntimeError("native BPE core unavailable (g++ build failed)")
        self._lib = lib
        text = "\n".join(self.SEP.join(pair) for pair in merges)
        self._handle = lib.bpe_new(text.encode("utf-8"))
        self._buf = ctypes.create_string_buffer(1 << 16)

    @staticmethod
    def available() -> bool:
        return _load() is not None

    def encode_word(self, symbols: List[str]) -> List[str]:
        word = self.SEP.join(symbols).encode("utf-8")
        n = self._lib.bpe_encode_word(self._handle, word, self._buf,
                                      len(self._buf))
        if n < 0:  # pathological word longer than the buffer
            raise ValueError("word too long for native BPE buffer")
        return self._buf.raw[:n].decode("utf-8").split(self.SEP)

    def __del__(self):
        if getattr(self, "_handle", None) and getattr(self, "_lib", None):
            self._lib.bpe_free(self._handle)
            self._handle = None
