"""ctypes bridge to the native C++ runtime (native/ → libpaddle_tpu_native.so).

Reference parity: this plays the role of paddle/fluid/pybind for the
non-compute runtime — the reference binds its C++ monitor
(platform/monitor.h:43), profiler (platform/profiler.h:126) and
DataFeed/Dataset engine (framework/data_feed.h:108, data_set.h) into Python;
we do the same over a C ABI with ctypes (pybind11 is not in the image).
The XLA compute path never goes through here — jax owns device memory and
kernels; this library is host-side runtime only (threadpool, channels, file
parsing/shuffle/batch assembly, stats, host trace events).

The library is a function of the committed sources: it is (re)built with
`make -C native` whenever `native/build/` (git-ignored) is missing or older
than any file under `native/src`, `native/include` or the Makefile.  Where
there is no toolchain (`make`/`g++` absent) `available()` is False and
callers use their pure-Python implementations; a build that *fails* raises
with the compiler's output.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import shutil
import subprocess
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libpaddle_tpu_native.so")

_lib = None
_lib_lock = threading.Lock()


def _sources_mtime() -> float:
    newest = os.path.getmtime(os.path.join(_NATIVE_DIR, "Makefile"))
    for sub in ("src", "include"):
        for root, _dirs, files in os.walk(os.path.join(_NATIVE_DIR, sub)):
            for f in files:
                newest = max(newest, os.path.getmtime(os.path.join(root, f)))
    return newest


def _lib_current() -> bool:
    return (os.path.exists(_LIB_PATH)
            and os.path.getmtime(_LIB_PATH) >= _sources_mtime())


def _ensure_built() -> bool:
    """Bring the .so up to date with the sources; False only where it
    cannot be built at all (no native tree, no toolchain)."""
    if not os.path.isdir(_NATIVE_DIR):
        return False
    if _lib_current():
        return True
    if not (shutil.which("make") and shutil.which(os.environ.get("CXX",
                                                                  "g++"))):
        return False
    build_dir = os.path.dirname(_LIB_PATH)
    os.makedirs(build_dir, exist_ok=True)
    # one builder at a time: tests and launch workers import concurrently
    with open(os.path.join(build_dir, ".lock"), "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        if _lib_current():
            return True
        proc = subprocess.run(["make", "-C", _NATIVE_DIR, "-j4"],
                              capture_output=True, text=True, timeout=600)
    if proc.returncode != 0 or not os.path.exists(_LIB_PATH):
        raise RuntimeError(
            f"`make -C {_NATIVE_DIR}` failed (exit {proc.returncode}):\n"
            + proc.stdout[-2000:] + proc.stderr[-4000:])
    return True


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.pt_stat_add.argtypes = [c.c_char_p, c.c_longlong]
    lib.pt_stat_set.argtypes = [c.c_char_p, c.c_longlong]
    lib.pt_stat_get.argtypes = [c.c_char_p]
    lib.pt_stat_get.restype = c.c_longlong
    lib.pt_stat_reset.argtypes = [c.c_char_p]
    lib.pt_stat_list.argtypes = [c.c_char_p, c.c_int]
    lib.pt_stat_list.restype = c.c_int

    lib.pt_prof_enabled.restype = c.c_int
    lib.pt_prof_push.argtypes = [c.c_char_p]
    lib.pt_prof_add_span.argtypes = [c.c_char_p, c.c_longlong, c.c_longlong]
    lib.pt_prof_export_chrome.argtypes = [c.c_char_p]
    lib.pt_prof_export_chrome.restype = c.c_int
    lib.pt_prof_summary.argtypes = [c.c_char_p, c.c_int]
    lib.pt_prof_summary.restype = c.c_int
    lib.pt_prof_summary_sorted.argtypes = [c.c_char_p, c.c_char_p, c.c_int]
    lib.pt_prof_summary_sorted.restype = c.c_int

    lib.pd_aes_ctr_crypt.argtypes = [c.c_char_p, c.c_int, c.c_char_p,
                                     c.POINTER(c.c_uint8), c.c_longlong]
    lib.pd_aes_ctr_crypt.restype = c.c_int
    lib.pd_aes_encrypt_block.argtypes = [c.c_char_p, c.c_int, c.c_char_p,
                                         c.POINTER(c.c_uint8)]
    lib.pd_aes_encrypt_block.restype = c.c_int

    lib.pt_feed_create.argtypes = [c.c_char_p, c.c_int, c.c_int, c.c_int]
    lib.pt_feed_create.restype = c.c_void_p
    lib.pt_feed_set_files.argtypes = [c.c_void_p, c.c_char_p]
    lib.pt_feed_load_into_memory.argtypes = [c.c_void_p]
    lib.pt_feed_load_into_memory.restype = c.c_int
    lib.pt_feed_shuffle.argtypes = [c.c_void_p, c.c_ulonglong]
    lib.pt_feed_num_samples.argtypes = [c.c_void_p]
    lib.pt_feed_num_samples.restype = c.c_int
    lib.pt_feed_float_dim.argtypes = [c.c_void_p]
    lib.pt_feed_float_dim.restype = c.c_int
    lib.pt_feed_int_dim.argtypes = [c.c_void_p]
    lib.pt_feed_int_dim.restype = c.c_int
    lib.pt_feed_start.argtypes = [c.c_void_p, c.c_int]
    lib.pt_feed_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
    lib.pt_feed_next.restype = c.c_int
    lib.pt_feed_release_memory.argtypes = [c.c_void_p]
    lib.pt_feed_destroy.argtypes = [c.c_void_p]


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not _ensure_built():
            return None
        lib = ctypes.CDLL(_LIB_PATH)
        _declare(lib)
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------- monitor --
# ref platform/monitor.h STAT_ADD/STAT_RESET; pure-python fallback registry.
_py_stats: Dict[str, int] = {}
_py_stats_lock = threading.Lock()


def stat_add(name: str, value: int = 1) -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_stat_add(name.encode(), int(value))
    else:
        with _py_stats_lock:
            _py_stats[name] = _py_stats.get(name, 0) + int(value)


def stat_set(name: str, value: int) -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_stat_set(name.encode(), int(value))
    else:
        with _py_stats_lock:
            _py_stats[name] = int(value)


def stat_get(name: str) -> int:
    lib = get_lib()
    if lib is not None:
        return int(lib.pt_stat_get(name.encode()))
    with _py_stats_lock:
        return _py_stats.get(name, 0)


def stat_reset(name: str) -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_stat_reset(name.encode())
    else:
        with _py_stats_lock:
            _py_stats[name] = 0


def stat_list() -> Dict[str, int]:
    lib = get_lib()
    if lib is None:
        with _py_stats_lock:
            return dict(_py_stats)
    # The registry can grow between the size query and the fill (native
    # worker threads add stats concurrently): retry until the buffer fits.
    need = lib.pt_stat_list(None, 0)
    while True:
        buf = ctypes.create_string_buffer(need + 64)
        got = lib.pt_stat_list(buf, need + 64)
        if got <= need + 63:
            break
        need = got
    out: Dict[str, int] = {}
    for line in buf.value.decode().splitlines():
        if "=" in line:
            k, v = line.rsplit("=", 1)
            out[k] = int(v)
    return out


# --------------------------------------------------------------- profiler --
def prof_enable() -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_prof_enable()


def prof_disable() -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_prof_disable()


def prof_enabled() -> bool:
    lib = get_lib()
    return bool(lib and lib.pt_prof_enabled())


def prof_push(name: str) -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_prof_push(name.encode())


def prof_pop() -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_prof_pop()


def prof_add_span(name: str, start_ns: int, end_ns: int) -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_prof_add_span(name.encode(), int(start_ns), int(end_ns))


def prof_clear() -> None:
    lib = get_lib()
    if lib is not None:
        lib.pt_prof_clear()


def prof_export_chrome(path: str) -> int:
    lib = get_lib()
    if lib is None:
        return -1
    return int(lib.pt_prof_export_chrome(path.encode()))


def prof_summary(sorted_key: Optional[str] = None) -> str:
    lib = get_lib()
    if lib is None:
        return ""
    key = (sorted_key or "total").encode()
    fill = lambda buf, n: lib.pt_prof_summary_sorted(key, buf, n)  # noqa: E731
    # Same grow-and-retry as stat_list: events can land between the size
    # query and the fill.
    need = fill(None, 0)
    while True:
        buf = ctypes.create_string_buffer(need + 256)
        got = fill(buf, need + 256)
        if got <= need + 255:
            return buf.value.decode()
        need = got


# --------------------------------------------------------------- datafeed --
class NativeDataFeed:
    """Python handle on the C++ multi-slot feed engine.

    slots: sequence of (name, dtype, dim) with dtype in {"float32","int64"};
    each produced batch is a dict name -> np.ndarray[batch, dim].
    Mirrors the InMemoryDataset flow (fluid/dataset.py:328):
    set_filelist → load_into_memory → local_shuffle → iterate.
    """

    def __init__(self, slots: Sequence[Tuple[str, str, int]], batch_size: int,
                 capacity: int = 8, num_threads: int = 4):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native runtime unavailable (g++/make build failed)")
        self._lib = lib
        self.slots = [(str(n), str(t), int(d)) for n, t, d in slots]
        for n, _, d in self.slots:
            if ";" in n or ":" in n:
                raise ValueError(f"slot name {n!r} may not contain ';' or ':'")
            if d <= 0:
                raise ValueError(f"slot {n!r} dim must be positive, got {d}")
        self.batch_size = int(batch_size)
        self._epoch_gen = 0
        spec = ";".join(
            f"{n}:{'i' if t in ('int64', 'int32', 'int') else 'f'}:{d}"
            for n, t, d in self.slots)
        self._h = lib.pt_feed_create(spec.encode(), self.batch_size,
                                     int(capacity), int(num_threads))
        if not self._h:
            raise ValueError(f"bad slot spec: {spec!r}")
        self._fdim = lib.pt_feed_float_dim(self._h)
        self._idim = lib.pt_feed_int_dim(self._h)

    def set_filelist(self, files: Sequence[str]) -> None:
        self._lib.pt_feed_set_files(self._h, ";".join(files).encode())

    def load_into_memory(self) -> int:
        n = self._lib.pt_feed_load_into_memory(self._h)
        if n < 0:
            raise IOError("datafeed: failed to read input files")
        return n

    def local_shuffle(self, seed: int = 0) -> None:
        self._lib.pt_feed_shuffle(self._h, int(seed))

    @property
    def num_samples(self) -> int:
        return self._lib.pt_feed_num_samples(self._h)

    def __iter__(self):
        # One live epoch per feed: starting a new iterator restarts the
        # native assembler, so any older iterator must not keep pulling from
        # the reopened queue — it checks its generation token and fails fast.
        self._epoch_gen += 1
        gen = self._epoch_gen
        self._lib.pt_feed_start(self._h, 0)
        fbuf = np.empty((self.batch_size, self._fdim), dtype=np.float32)
        ibuf = np.empty((self.batch_size, self._idim), dtype=np.int64)
        while True:
            if gen != self._epoch_gen:
                raise RuntimeError(
                    "a new epoch was started on this feed; the previous "
                    "iterator is invalid (one live iterator per feed)")
            rows = self._lib.pt_feed_next(
                self._h,
                fbuf.ctypes.data_as(ctypes.c_void_p) if self._fdim else None,
                ibuf.ctypes.data_as(ctypes.c_void_p) if self._idim else None)
            if rows <= 0:
                return
            yield self._split(fbuf[:rows], ibuf[:rows])

    def _split(self, fmat: np.ndarray, imat: np.ndarray):
        out = {}
        foff = ioff = 0
        for name, t, d in self.slots:
            if t in ("int64", "int32", "int"):
                out[name] = imat[:, ioff:ioff + d].copy()
                ioff += d
            else:
                out[name] = fmat[:, foff:foff + d].copy()
                foff += d
        return out

    def release_memory(self) -> None:
        self._lib.pt_feed_release_memory(self._h)

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            try:
                self._lib.pt_feed_destroy(h)
            except Exception:
                pass
