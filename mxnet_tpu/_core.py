"""ctypes bindings to the native runtime (src/ -> libmxtpu.so).

TPU-native counterpart of the reference's _LIB loading
(reference python/mxnet/base.py _LIB + check_call).  The native library
provides the host-side runtime: dependency-scheduling engine, RecordIO
framing, and the threaded image decode pipeline.  Pure-Python fallbacks
exist for everything, so the package works without the build; `lib()`
builds on demand with make when a toolchain is present.
"""
import ctypes
import fcntl
import os
import subprocess
import threading

_LIB = None
_TRIED = False
_LOCK = threading.Lock()

_LIB_PATH = os.path.join(os.path.dirname(__file__), 'libmxtpu.so')
_SRC_DIR = os.path.join(os.path.dirname(__file__), '..', 'src')


class NativeError(RuntimeError):
    pass


def _build():
    """Build the library unless another process has: one builder at a
    time under an exclusive lock on src/.build.lock (pytest-xdist's
    workers all import this at once on a fresh checkout), and the link
    lands under a temporary name that os.replace moves into place, so
    nobody ever loads a half-written library."""
    with open(os.path.join(_SRC_DIR, '.build.lock'), 'w') as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if os.path.exists(_LIB_PATH):
            return
        tmp = '%s.%d.tmp' % (_LIB_PATH, os.getpid())
        try:
            subprocess.check_call(
                ['make', '-s', '-j4', 'TARGET=' + tmp], cwd=_SRC_DIR,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            os.replace(tmp, _LIB_PATH)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)


def _declare(lib):
    lib.MXTGetLastError.restype = ctypes.c_char_p
    lib.MXTEngineCreate.restype = ctypes.c_void_p
    lib.MXTEngineCreate.argtypes = [ctypes.c_int]
    lib.MXTEngineFree.argtypes = [ctypes.c_void_p]
    lib.MXTEngineNewVar.restype = ctypes.c_int64
    lib.MXTEngineNewVar.argtypes = [ctypes.c_void_p]
    lib.MXTEnginePush.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.MXTEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.MXTEngineWaitAll.argtypes = [ctypes.c_void_p]
    lib.MXTEngineDeleteVar.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.MXTRecordReaderCreate.restype = ctypes.c_void_p
    lib.MXTRecordReaderCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordReaderFree.argtypes = [ctypes.c_void_p]
    lib.MXTRecordReaderNext.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_uint64)]
    lib.MXTRecordReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.MXTRecordWriterCreate.restype = ctypes.c_void_p
    lib.MXTRecordWriterCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordWriterFree.argtypes = [ctypes.c_void_p]
    lib.MXTRecordWriterWrite.restype = ctypes.c_int64
    lib.MXTRecordWriterWrite.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
    lib.MXTImageRecordIterCreate.restype = ctypes.c_void_p
    lib.MXTImageRecordIterCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_uint64]
    lib.MXTImageRecordIterFree.argtypes = [ctypes.c_void_p]
    lib.MXTImageRecordIterNext.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.c_int)]
    lib.MXTImageRecordIterReset.argtypes = [ctypes.c_void_p]
    return lib


def lib(required=False):
    """Returns the loaded native library, building it if necessary, or
    None when unavailable (callers then use the pure-Python path)."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None:
            return _LIB
        if _TRIED and not required:
            return None
        _TRIED = True
        if os.environ.get('MXTPU_NO_NATIVE'):
            if required:
                raise NativeError('native runtime disabled by '
                                  'MXTPU_NO_NATIVE')
            return None
        try:
            if not os.path.exists(_LIB_PATH):
                _build()
            _LIB = _declare(ctypes.CDLL(_LIB_PATH))
        except (OSError, subprocess.CalledProcessError) as e:
            if required:
                raise NativeError('failed to build/load native runtime: '
                                  '%s' % e)
            return None
        return _LIB


def available():
    return lib() is not None


def check_call(ret):
    """Raise with the native error message on non-zero return
    (reference base.py check_call)."""
    if ret != 0:
        raise NativeError(lib().MXTGetLastError().decode())
