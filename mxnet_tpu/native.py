"""Native library loader (ctypes bridge to src/*.cc).

The runtime's host-side hot paths are C++ (SURVEY.md requirement: native
components for the IO/runtime layer, like the reference's dmlc-core/C++
iterators).  The shared object is built on demand with g++ the first time
it's needed and cached next to the package (mxnet_tpu/_native/, git-ignored:
a checkout builds from src/*.cc, nothing built is committed);
`setup.py build_native` does the same ahead of time.  Pure-Python fallbacks
keep everything working if no toolchain is present — a failed build is
logged once with the compiler's stderr, then the fallback runs.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from . import locks

__all__ = ["get_recordio_lib", "get_imdecode_lib", "NativeImageDecoder"]

_LOCK = locks.lock("native.build")
_LIB = {}

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_native")


def _build(name, sources, extra=()):
    os.makedirs(_BUILD_DIR, exist_ok=True)
    out = os.path.join(_BUILD_DIR, "lib%s.so" % name)
    srcs = [os.path.join(_SRC_DIR, s) for s in sources]
    if os.path.exists(out) and all(
        os.path.getmtime(out) >= os.path.getmtime(s) for s in srcs
    ):
        return out
    # link to a private name, then rename: another process building the
    # same library (a launcher's ranks on a fresh checkout) never loads
    # a half-written file
    tmp = "%s.%d.tmp" % (out, os.getpid())
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp] + srcs + list(extra)
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return out


_FAILED = set()


def _log_build_failure(name, err):
    """One warning per library per process."""
    if name in _FAILED:
        return
    _FAILED.add(name)
    detail = err.stderr if isinstance(err, subprocess.CalledProcessError) \
        else repr(err)
    logging.warning("native library %r unavailable, using the Python "
                    "fallback: %s", name, (detail or "").strip())


def _load(name, sources, extra=()):
    with _LOCK:
        if name in _LIB:
            return _LIB[name]
        try:
            # mxlint: disable=E009 -- build-once gate: concurrent first-callers must wait for ONE g++ run
            path = _build(name, sources, extra)
            lib = ctypes.CDLL(path)
        except (OSError, subprocess.CalledProcessError) as e:
            # no g++ (FileNotFoundError), a compile/link error, or a
            # library the loader rejects
            _log_build_failure(name, e)
            lib = None
        _LIB[name] = lib
        return lib


def _embed_flags():
    """g++ flags to embed CPython (include dir + shared libpython), or
    None when this interpreter has no shared library to embed."""
    import sysconfig

    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ldlib = sysconfig.get_config_var("LDLIBRARY") or ""
    if ".so" not in ldlib:
        # static-only python build: INSTSONAME usually names the shared one
        ldlib = sysconfig.get_config_var("INSTSONAME") or ldlib
    if ".so" not in ldlib:
        return None  # no shared libpython to embed
    # link by the detected library name, not a guessed stem: covers debug
    # suffixes (libpython3.Xd.so) and soname-only installs (.so.1.0)
    if ldlib.endswith(".so"):
        link = "-l%s" % ldlib[len("lib"):-len(".so")]
    else:
        link = "-l:%s" % ldlib
    return ["-I%s" % inc, "-L%s" % libdir, link, "-Wl,-rpath,%s" % libdir]


def _embedded_lib_path(name, sources):
    """Build (if needed) a CPython-embedding C ABI library.

    These .so files are meant to be linked by non-Python processes, so
    they carry the interpreter on the link line; the cache invalidates on
    flag changes (interpreter moved) and on py_embed.h edits, which the
    plain source-mtime check cannot see."""
    extra = _embed_flags()
    if extra is None:
        return None
    with _LOCK:
        try:
            flags_path = os.path.join(_BUILD_DIR, "lib%s.flags" % name)
            hdr = os.path.join(_SRC_DIR, "py_embed.h")
            flags = " ".join(extra)
            if os.path.exists(hdr):
                flags += " py_embed.h:%d" % int(os.path.getmtime(hdr))
            old = None
            if os.path.exists(flags_path):
                with open(flags_path) as f:
                    old = f.read()
            out = os.path.join(_BUILD_DIR, "lib%s.so" % name)
            if old != flags and os.path.exists(out):
                os.remove(out)
            # mxlint: disable=E009 -- same build-once gate as _load: one compile, callers wait for its result
            path = _build(name, sources, extra)
            os.makedirs(_BUILD_DIR, exist_ok=True)
            with open(flags_path, "w") as f:
                f.write(flags)
            return path
        except (OSError, subprocess.CalledProcessError) as e:
            _log_build_failure(name, e)
            return None


def get_predict_lib_path():
    """The predict-only C ABI library (c_predict_api.h surface)."""
    return _embedded_lib_path("mxnet_tpu_predict", ["c_predict_api.cc"])


def get_c_api_lib_path():
    """The FULL C ABI library: core c_api.h (NDArray / op invoke / Symbol
    / Executor / KVStore) plus the whole c_predict_api.h surface."""
    return _embedded_lib_path("mxnet_tpu",
                              ["c_predict_api.cc", "c_api.cc"])


def get_recordio_lib():
    """Load (building if needed) the native RecordIO engine; None if no
    toolchain."""
    lib = _load("recordio", ["recordio.cc"])
    if lib is None:
        return None
    if not getattr(lib, "_rio_configured", False):
        lib.rio_open_reader.restype = ctypes.c_void_p
        lib.rio_open_reader.argtypes = [ctypes.c_char_p]
        lib.rio_close_reader.argtypes = [ctypes.c_void_p]
        lib.rio_seek.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.rio_tell.restype = ctypes.c_long
        lib.rio_tell.argtypes = [ctypes.c_void_p]
        lib.rio_read_batch.restype = ctypes.c_long
        lib.rio_read_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.rio_index.restype = ctypes.c_long
        lib.rio_index.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_long), ctypes.c_long]
        lib.rio_read_at.restype = ctypes.c_long
        lib.rio_read_at.argtypes = [ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p, ctypes.c_long]
        lib.rio_open_writer.restype = ctypes.c_void_p
        lib.rio_open_writer.argtypes = [ctypes.c_char_p]
        lib.rio_write.restype = ctypes.c_long
        lib.rio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_long]
        lib.rio_close_writer.argtypes = [ctypes.c_void_p]
        lib._rio_configured = True
    return lib


def get_im2rec_lib():
    """Load (building if needed) the native multithreaded image packer
    (src/im2rec.cc, reference tools/im2rec.cc analog); None if no
    toolchain or no libjpeg."""
    lib = _load("im2rec", ["im2rec.cc", "recordio.cc"],
                extra=tuple(_jpeg_link_flags()))
    if lib is None:
        return None
    if not getattr(lib, "_im2rec_configured", False):
        lib.im2rec_pack.restype = ctypes.c_long
        lib.im2rec_pack.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_long,
        ]
        lib._im2rec_configured = True
    return lib


def im2rec_pack(lst_path, image_root, rec_path, idx_path, resize=0,
                quality=95, nthreads=0):
    """Pack a .lst into .rec/.idx with the native threaded packer.
    Returns the number of records written; raises on failure."""
    lib = get_im2rec_lib()
    if lib is None:
        raise RuntimeError("native im2rec unavailable (toolchain/libjpeg)")
    if nthreads <= 0:
        nthreads = os.cpu_count() or 1
    err = ctypes.create_string_buffer(512)
    n = lib.im2rec_pack(str(lst_path).encode(), str(image_root).encode(),
                        str(rec_path).encode(), str(idx_path).encode(),
                        int(resize), int(quality), int(nthreads), err,
                        len(err))
    if n < 0:
        raise IOError("im2rec_pack: %s" % err.value.decode())
    if err.value:
        import logging

        logging.warning("im2rec_pack: %s", err.value.decode())
    return int(n)


class NativeRecordReader:
    """Batched native reader over a .rec file."""

    def __init__(self, path):
        self._lib = get_recordio_lib()
        if self._lib is None:
            raise RuntimeError("native recordio unavailable")
        self._h = self._lib.rio_open_reader(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)
        self._buf_cap = 1 << 20
        self._buf = ctypes.create_string_buffer(self._buf_cap)

    def read_batch(self, n):
        """Return a list of up to n record payloads (bytes); [] at EOF."""
        out = []
        sizes = (ctypes.c_long * n)()
        while len(out) < n:
            want = n - len(out)
            got = self._lib.rio_read_batch(self._h, want, self._buf, self._buf_cap, sizes)
            if got == -2:  # next record larger than buffer: grow and retry
                self._buf_cap *= 4
                self._buf = ctypes.create_string_buffer(self._buf_cap)
                continue
            if got == -1:
                raise IOError("corrupt RecordIO stream")
            if got == 0:  # EOF
                break
            off = 0
            raw = self._buf.raw
            for i in range(got):
                out.append(raw[off : off + sizes[i]])
                off += sizes[i]
        return out

    def read_at(self, offset):
        while True:
            got = self._lib.rio_read_at(self._h, offset, self._buf, self._buf_cap)
            if got == -2:
                self._buf_cap *= 4
                self._buf = ctypes.create_string_buffer(self._buf_cap)
                continue
            if got == -1:
                raise IOError("corrupt RecordIO record at %d" % offset)
            return self._buf.raw[:got]

    def seek(self, offset):
        self._lib.rio_seek(self._h, offset)

    def close(self):
        if self._h:
            self._lib.rio_close_reader(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def native_index(path):
    """Offsets of every record in the file (native full-file scan)."""
    lib = get_recordio_lib()
    if lib is None:
        raise RuntimeError("native recordio unavailable")
    cap = 1 << 16
    while True:
        offsets = (ctypes.c_long * cap)()
        count = lib.rio_index(path.encode(), offsets, cap)
        if count < 0:
            raise IOError("corrupt RecordIO file %s" % path)
        if count <= cap:
            return list(offsets[:count])
        cap = count


def _jpeg_link_flags():
    """Prefer a SIMD libjpeg-turbo (ABI 62, e.g. Pillow's bundled copy —
    ~3-4x faster huffman+IDCT than classic libjpeg62) over the system lib."""
    import glob
    import sysconfig

    site = os.path.dirname(os.path.dirname(sysconfig.get_paths()["purelib"]))
    for pat in (
        os.path.join(sysconfig.get_paths()["purelib"], "pillow.libs", "libjpeg-*.so.62*"),
        os.path.join(site, "**", "pillow.libs", "libjpeg-*.so.62*"),
    ):
        hits = sorted(glob.glob(pat, recursive=True))
        if hits:
            return [hits[0], "-Wl,-rpath," + os.path.dirname(hits[0]), "-pthread"]
    return ["-ljpeg", "-pthread"]


def get_imdecode_lib():
    """Load (building if needed) the native JPEG decode engine
    (src/imdecode.cc over libjpeg-turbo/libjpeg); None if unavailable."""
    lib = _load("imdecode", ["imdecode.cc"], extra=tuple(_jpeg_link_flags()))
    if lib is None:
        return None
    if not getattr(lib, "_imdec_configured", False):
        lib.imdec_batch.restype = ctypes.c_long
        lib.imdec_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_long),
            ctypes.c_long, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_ubyte),
            ctypes.POINTER(ctypes.c_float), ctypes.c_float, ctypes.c_int,
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ]
        lib._imdec_configured = True
    return lib


class NativeImageDecoder:
    """Batched JPEG decode+resize+crop+normalize (reference analog:
    src/io/iter_image_recordio_2.cc OMP decode loop).  One ctypes call
    decodes a whole batch on a C++ thread pool; per-image failures are
    reported for a Python fallback (PNG/raw records)."""

    LAYOUT_CHW_F32 = 0
    LAYOUT_HWC_F32 = 1
    LAYOUT_HWC_U8 = 2

    def __init__(self, nthreads=8):
        self._lib = get_imdecode_lib()
        if self._lib is None:
            raise RuntimeError("native imdecode unavailable")
        # oversubscribing physical cores degrades decode throughput
        self.nthreads = max(1, min(int(nthreads), os.cpu_count() or 1))

    def decode_batch(self, payloads, out, crop_u, crop_v, mirror,
                     mean, scale=1.0, resize_short=0, layout=0):
        """Decode `payloads` (list of bytes) into preallocated numpy `out`.

        out: (n, c, h, w) f32 / (n, h, w, c) f32 / (n, h, w, c) u8 per layout.
        crop_u/crop_v: per-image crop position in [0, 1] (0.5 = center).
        Returns a numpy int32 status array (0 ok, -1 needs fallback)."""
        import numpy as np

        n = len(payloads)
        if layout == self.LAYOUT_CHW_F32:
            c, h, w = out.shape[1:]
        else:
            h, w, c = out.shape[1:]
        bufs = (ctypes.c_char_p * n)(*payloads)
        lens = (ctypes.c_long * n)(*[len(p) for p in payloads])
        cu = np.ascontiguousarray(crop_u, dtype=np.float32)
        cv = np.ascontiguousarray(crop_v, dtype=np.float32)
        mir = np.ascontiguousarray(mirror, dtype=np.uint8)
        mn = np.ascontiguousarray(mean, dtype=np.float32)
        if mn.size < c:
            mn = np.resize(mn, (c,)).astype(np.float32)
        status = np.zeros((n,), dtype=np.int32)
        self._lib.imdec_batch(
            bufs, lens, n, h, w, c, int(resize_short),
            cu.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            cv.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            mir.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
            mn.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_float(scale), int(layout),
            out.ctypes.data_as(ctypes.c_void_p),
            status.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
            self.nthreads,
        )
        return status
