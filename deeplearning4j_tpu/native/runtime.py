"""ctypes binding for the dl4j-tpu native PJRT runtime.

Reference parity: ``nd4j-native``'s JNI bridge onto libnd4j's NativeOps
(SURVEY.md §2.1 L0) — here a ctypes bridge onto
``libdl4j_tpu_native.so`` (built from ``src/pjrt_runtime.cc``), which owns
PJRT plugin loading, client/device lifetime, host<->device transfers,
StableHLO compilation with an executable cache, and synchronous execution.

Typical use::

    rt = NativeRuntime.create()            # loads $DL4J_TPU_PJRT_PLUGIN
    mlir = jax.jit(f).lower(*args).as_text()   # StableHLO from any tracer
    exe = rt.compile(mlir)
    outs = exe(x, y)                        # numpy in, numpy out

This is the L0 seam a non-Python frontend would target: nothing above the
C ABI requires jax (jax is used here only as a convenient StableHLO
*producer* in tests/examples).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import time
from typing import List, Optional

import numpy as np

from deeplearning4j_tpu import profiler as _prof

# Registered at import so GET /metrics always exposes the compile-cache
# and transfer counters (zero until the native path runs) — scrapers and
# the bench harness can rely on the series existing.
_REG = _prof.get_registry()
_M_CACHE_HITS = _REG.counter(
    "dl4j_native_compile_cache_hits_total",
    "Native runtime executable-cache hits (dl4j_compile)")
_M_CACHE_MISSES = _REG.counter(
    "dl4j_native_compile_cache_misses_total",
    "Native runtime executable-cache misses (fresh PJRT compilations)")
_M_COMPILE_SECONDS = _REG.histogram(
    "dl4j_native_compile_seconds",
    "StableHLO -> PJRT LoadedExecutable compile latency",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
_M_H2D_BYTES = _REG.counter(
    "dl4j_native_h2d_bytes_total",
    "Host->device bytes staged through dl4j_execute inputs")
_M_D2H_BYTES = _REG.counter(
    "dl4j_native_d2h_bytes_total",
    "Device->host bytes returned from dl4j_execute outputs")
_M_EXECUTE_SECONDS = _REG.histogram(
    "dl4j_native_execute_seconds",
    "Synchronous dl4j_execute round-trip latency (H2D + run + D2H)")

_THIS_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_THIS_DIR, "libdl4j_tpu_native.so")
#: the PJRT C-API plugin to dlopen — a deployment setting with no default
PLUGIN_ENV = "DL4J_TPU_PJRT_PLUGIN"


class NativeRuntimeError(RuntimeError):
    pass


# PJRT_Buffer_Type enum values (pjrt_c_api.h) <-> numpy dtypes
_PJRT_INVALID, _PJRT_PRED = 0, 1
_DTYPE_TO_PJRT = {
    np.dtype(np.bool_): 1,
    np.dtype(np.int8): 2, np.dtype(np.int16): 3, np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.uint8): 6, np.dtype(np.uint16): 7, np.dtype(np.uint32): 8,
    np.dtype(np.uint64): 9,
    np.dtype(np.float16): 10, np.dtype(np.float32): 11,
    np.dtype(np.float64): 12,
    # 13 = BF16 (ml_dtypes), added below
    np.dtype(np.complex64): 14, np.dtype(np.complex128): 15,
}
try:
    import ml_dtypes
    _DTYPE_TO_PJRT[np.dtype(ml_dtypes.bfloat16)] = 13
except ImportError:                                   # pragma: no cover
    pass
_PJRT_TO_DTYPE = {v: k for k, v in _DTYPE_TO_PJRT.items()}


class _HostBuffer(ctypes.Structure):
    _fields_ = [("data", ctypes.c_void_p),
                ("dtype", ctypes.c_int32),
                ("ndim", ctypes.c_int32),
                ("dims", ctypes.c_int64 * 16),
                ("nbytes", ctypes.c_int64)]


def build_native_lib(force: bool = False) -> str:
    """Build libdl4j_tpu_native.so with the in-tree Makefile if missing."""
    if os.path.exists(_LIB_PATH) and not force:
        return _LIB_PATH
    subprocess.run(["make", "-C", _THIS_DIR] + (["-B"] if force else []),
                   check=True, capture_output=True)
    return _LIB_PATH


def _load_lib() -> ctypes.CDLL:
    build_native_lib()
    lib = ctypes.CDLL(_LIB_PATH)
    lib.dl4j_client_create.restype = ctypes.c_void_p
    lib.dl4j_client_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_char_p, ctypes.c_size_t]
    lib.dl4j_client_destroy.argtypes = [ctypes.c_void_p]
    lib.dl4j_client_device_count.argtypes = [ctypes.c_void_p]
    lib.dl4j_client_device_count.restype = ctypes.c_int
    lib.dl4j_client_platform_name.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                              ctypes.c_size_t]
    lib.dl4j_client_platform_name.restype = ctypes.c_int
    lib.dl4j_client_api_version.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.dl4j_client_api_version.restype = ctypes.c_int
    lib.dl4j_compile.restype = ctypes.c_void_p
    lib.dl4j_compile.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_size_t]
    lib.dl4j_executable_release.argtypes = [ctypes.c_void_p]
    lib.dl4j_executable_num_outputs.argtypes = [ctypes.c_void_p]
    lib.dl4j_executable_num_outputs.restype = ctypes.c_int64
    lib.dl4j_client_cache_stats.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64)]
    lib.dl4j_client_cache_stats.restype = ctypes.c_int64
    lib.dl4j_execute.restype = ctypes.c_int
    lib.dl4j_execute.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int, ctypes.POINTER(_HostBuffer), ctypes.c_int,
        ctypes.c_char_p, ctypes.c_size_t]
    lib.dl4j_free_outputs.argtypes = [ctypes.POINTER(_HostBuffer),
                                      ctypes.c_int]
    return lib


_lib_singleton: Optional[ctypes.CDLL] = None


def _lib() -> ctypes.CDLL:
    global _lib_singleton
    if _lib_singleton is None:
        _lib_singleton = _load_lib()
    return _lib_singleton


def _default_compile_options() -> bytes:
    """Serialized CompileOptionsProto for a 1-replica program (produced via
    jaxlib's xla_client; the C++ layer itself is proto-free)."""
    from jax._src.lib import xla_client
    opts = xla_client.CompileOptions()
    return opts.SerializeAsString()


class NativeExecutable:
    """A compiled program (PJRT LoadedExecutable behind the C ABI)."""

    def __init__(self, runtime: "NativeRuntime", handle: int, cache_hit: bool):
        self._rt = runtime
        self._h = handle
        self.cache_hit = cache_hit

    @property
    def num_outputs(self) -> int:
        return int(_lib().dl4j_executable_num_outputs(self._h))

    def execute(self, *inputs, device: int = 0) -> List[np.ndarray]:
        arrs = [np.ascontiguousarray(np.asarray(a)) for a in inputs]
        n = len(arrs)
        _t0 = time.perf_counter()
        _M_H2D_BYTES.inc(sum(a.nbytes for a in arrs))
        data = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p) for a in arrs])
        dts = (ctypes.c_int32 * n)(*[_DTYPE_TO_PJRT[a.dtype] for a in arrs])
        nds = (ctypes.c_int32 * n)(*[a.ndim for a in arrs])
        flat_dims: List[int] = []
        for a in arrs:
            flat_dims.extend(a.shape)
        dims = (ctypes.c_int64 * max(1, len(flat_dims)))(*flat_dims)
        max_out = max(self.num_outputs, 1)
        outs = (_HostBuffer * max_out)()
        err = ctypes.create_string_buffer(2048)
        rc = _lib().dl4j_execute(self._h, n, data, dts, nds, dims, device,
                                 outs, max_out, err, len(err))
        if rc < 0:
            raise NativeRuntimeError(err.value.decode() or "execute failed")
        results = []
        for i in range(rc):
            hb = outs[i]
            dt = _PJRT_TO_DTYPE.get(hb.dtype)
            if dt is None:
                _lib().dl4j_free_outputs(outs, rc)
                raise NativeRuntimeError(f"unmapped output dtype {hb.dtype}")
            shape = tuple(hb.dims[d] for d in range(hb.ndim))
            n_elems = int(np.prod(shape)) if shape else 1
            if n_elems == 0:
                results.append(np.zeros(shape, dt))
                continue
            if hb.nbytes == 0 or not hb.data:
                _lib().dl4j_free_outputs(outs, rc)
                raise NativeRuntimeError(
                    f"output {i} has empty buffer for non-empty shape {shape}")
            # ONE host memcpy: view the runtime-owned buffer in place and
            # copy once before dl4j_free_outputs releases it (string_at +
            # frombuffer(...).copy() materialized every output twice)
            src = np.ctypeslib.as_array(
                ctypes.cast(hb.data, ctypes.POINTER(ctypes.c_uint8)),
                shape=(int(hb.nbytes),))
            results.append(src[:n_elems * dt.itemsize].view(dt)
                           .reshape(shape).copy())
        _lib().dl4j_free_outputs(outs, rc)
        _M_D2H_BYTES.inc(sum(r.nbytes for r in results))
        dt = time.perf_counter() - _t0
        _M_EXECUTE_SECONDS.observe(dt)
        if _prof.tracing_enabled():
            from deeplearning4j_tpu.profiler.tracer import _now_us
            _prof.get_tracer().add_event(
                "native:execute", _now_us() - dt * 1e6, dt * 1e6,
                {"n_inputs": n, "n_outputs": rc})
        return results

    __call__ = execute

    def release(self):
        if self._h:
            _lib().dl4j_executable_release(self._h)
            self._h = None


_SHARED_RUNTIME = None


def get_runtime() -> "NativeRuntime":
    """Process-wide shared client for framework execution paths (the
    ``backend="native"`` seam in autodiff.samediff). Raises
    NativeRuntimeError when the plugin/toolchain is unavailable — callers
    surface that as "native backend not available here"."""
    global _SHARED_RUNTIME
    if _SHARED_RUNTIME is None:
        _SHARED_RUNTIME = NativeRuntime.create()
    return _SHARED_RUNTIME


class NativeRuntime:
    """PJRT client owned by the native layer (ref: Nd4j backend init over
    NativeOps — SURVEY.md §2.1)."""

    def __init__(self, handle: int, plugin_path: str):
        self._h = handle
        self.plugin_path = plugin_path

    @classmethod
    def create(cls, plugin_path: str = None,
               create_options: dict = None) -> "NativeRuntime":
        plugin_path = plugin_path or os.environ.get(PLUGIN_ENV)
        if not plugin_path:
            raise NativeRuntimeError(
                f"no PJRT plugin: pass plugin_path or set {PLUGIN_ENV} to "
                f"the plugin's shared library")
        keys, types, strs, ints = [], [], [], []
        for k, v in (create_options or {}).items():
            keys.append(k.encode())
            if isinstance(v, str):
                types.append(0); strs.append(v.encode()); ints.append(0)
            else:
                types.append(1); strs.append(b""); ints.append(int(v))
        n = len(keys)
        err = ctypes.create_string_buffer(2048)
        h = _lib().dl4j_client_create(
            plugin_path.encode(), n,
            (ctypes.c_char_p * max(1, n))(*keys),
            (ctypes.c_int32 * max(1, n))(*types),
            (ctypes.c_char_p * max(1, n))(*strs),
            (ctypes.c_int64 * max(1, n))(*ints),
            err, len(err))
        if not h:
            raise NativeRuntimeError(
                f"client create failed for {plugin_path}: "
                f"{err.value.decode()}")
        return cls(h, plugin_path)

    @property
    def device_count(self) -> int:
        return int(_lib().dl4j_client_device_count(self._h))

    @property
    def platform_name(self) -> str:
        buf = ctypes.create_string_buffer(256)
        rc = _lib().dl4j_client_platform_name(self._h, buf, len(buf))
        if rc < 0:
            raise NativeRuntimeError("platform name query failed")
        return buf.value.decode()

    @property
    def api_version(self):
        mj, mn = ctypes.c_int(), ctypes.c_int()
        _lib().dl4j_client_api_version(self._h, ctypes.byref(mj),
                                       ctypes.byref(mn))
        return (mj.value, mn.value)

    def cache_stats(self):
        hits, misses = ctypes.c_int64(), ctypes.c_int64()
        size = _lib().dl4j_client_cache_stats(self._h, ctypes.byref(hits),
                                              ctypes.byref(misses))
        return {"size": int(size), "hits": int(hits.value),
                "misses": int(misses.value)}

    def compile(self, program, fmt: str = "mlir",
                compile_options: bytes = None) -> NativeExecutable:
        """Compile StableHLO MLIR text/bytecode (or serialized HLO proto
        with fmt='hlo'); cached by (program, options) content hash in
        the in-process executable cache."""
        if isinstance(program, str):
            program = program.encode()
        opts = compile_options if compile_options is not None \
            else _default_compile_options()
        hit = ctypes.c_int(0)
        err = ctypes.create_string_buffer(4096)
        with _prof.trace_span("native:compile", fmt=fmt,
                              program_bytes=len(program)):
            t0 = time.perf_counter()
            h = _lib().dl4j_compile(self._h, program, len(program),
                                    fmt.encode(), opts, len(opts),
                                    ctypes.byref(hit), err, len(err))
            dt = time.perf_counter() - t0
        if not h:
            raise NativeRuntimeError(err.value.decode() or "compile failed")
        if hit.value:
            _M_CACHE_HITS.inc()
        else:
            _M_CACHE_MISSES.inc()
            _M_COMPILE_SECONDS.observe(dt)
            # recompile-churn seam: each fresh program body this client
            # compiles is a distinct signature (steady-state training
            # should converge on a handful)
            from deeplearning4j_tpu.analysis import churn as _churn
            # owner=None: an unscoped site, so every model.validate()
            # surfaces a churning native cache (see churn.diagnostics_for)
            _churn.get_churn_detector().record(
                "native.compile", (hash(program), hash(opts)))
        return NativeExecutable(self, h, bool(hit.value))

    def close(self):
        if self._h:
            _lib().dl4j_client_destroy(self._h)
            self._h = None
