"""Central runtime environment / flag registry.

Reference parity: ND4J centralises every ``-D``/env knob in
``org.nd4j.common.config.{ND4JSystemProperties,ND4JEnvironmentVars}`` and
bridges JVM state to libnd4j's ``include/system/Environment.h`` via
``Nd4j.getEnvironment()`` (SURVEY.md §5 "Config / flag system").

Here the registry is a single process-wide :class:`Environment` singleton.
Every knob has (a) a typed attribute, (b) an environment-variable override
(``DL4J_TPU_*``), and (c) a docstring row in :data:`KNOBS` so the full
registry is introspectable (``Environment.describe()``).
"""

from __future__ import annotations

import os
import sys
import threading
from dataclasses import dataclass, field, fields
from typing import Any


def _env(name: str, default: Any, typ: type) -> Any:
    raw = os.environ.get(name)
    if raw is None:
        return default
    if typ is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return typ(raw)


@dataclass
class Environment:
    """Process-wide runtime flags (singleton via :meth:`get`)."""

    # -- debug / verbosity (ref: libnd4j Environment::setDebug/setVerbose) --
    debug: bool = field(default_factory=lambda: _env("DL4J_TPU_DEBUG", False, bool))
    verbose: bool = field(default_factory=lambda: _env("DL4J_TPU_VERBOSE", False, bool))

    # -- numerics (ref: OpExecutioner ProfilingMode NAN_PANIC/INF_PANIC) --
    nan_panic: bool = field(default_factory=lambda: _env("DL4J_TPU_NAN_PANIC", False, bool))
    inf_panic: bool = field(default_factory=lambda: _env("DL4J_TPU_INF_PANIC", False, bool))

    # -- precision policy: compute dtype for matmul/conv on the MXU --
    # bf16 matmuls with f32 accumulation are the TPU-native default; set to
    # "float32" ("highest") to force full-precision MXU passes.
    matmul_precision: str = field(
        default_factory=lambda: _env("DL4J_TPU_MATMUL_PRECISION", "bfloat16", str)
    )

    # -- profiling (ref: OpProfiler / ProfilingListener) --
    profiling: bool = field(default_factory=lambda: _env("DL4J_TPU_PROFILING", False, bool))
    profile_dir: str = field(default_factory=lambda: _env("DL4J_TPU_PROFILE_DIR", "/tmp/dl4j_tpu_profile", str))

    # -- data pipeline --
    prefetch_buffer: int = field(default_factory=lambda: _env("DL4J_TPU_PREFETCH", 2, int))
    loader_threads: int = field(default_factory=lambda: _env("DL4J_TPU_LOADER_THREADS", 4, int))

    _instance = None
    _lock = threading.Lock()

    @classmethod
    def get(cls) -> "Environment":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._instance = None

    def describe(self) -> str:
        """Human-readable registry of every knob and its current value."""
        rows = []
        for f in fields(self):
            if f.name.startswith("_"):
                continue
            env_var = "DL4J_TPU_" + f.name.upper()
            rows.append(f"{f.name:<22} {env_var:<28} = {getattr(self, f.name)!r}")
        return "\n".join(rows)


KNOBS = {
    "debug": "Verbose per-op debug logging (ref: libnd4j Environment::setDebug)",
    "verbose": "Extra execution logging (ref: Environment::setVerbose)",
    "nan_panic": "Raise if any op output contains NaN (ref: ProfilingMode.NAN_PANIC)",
    "inf_panic": "Raise if any op output contains Inf (ref: ProfilingMode.INF_PANIC)",
    "matmul_precision": "MXU compute precision: bfloat16|tensorfloat32|float32",
    "profiling": "Enable per-op profiling (ref: OpProfiler)",
    "profile_dir": "Directory for Chrome-trace profiles (ref: ProfilingListener)",
    "prefetch_buffer": "Async iterator prefetch depth (ref: AsyncDataSetIterator)",
    "loader_threads": "Host data-loading threads (ref: libnd4j Threads, data only)",
}


#: where JAX's persistent compilation cache lives when nothing outside
#: placed it: fixed by this file's own location, because the path is part
#: of the cache's key — a directory that moves never hits
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_jax_compile_cache() -> str:
    """Say where JAX's persistent compilation cache lives, and return the
    directory. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and
    nothing is changed. Unset: ``<checkout>/.jax_cache``. Called by the
    entry points (``chip_smoke.py``, ``bench.py``) before their first
    compile, never at package import — the test suite must not fill the
    checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE_DIR)
    return DEFAULT_JAX_CACHE_DIR


def jax_compile_cache_status():
    """``(directory | None, writable)``: is JAX's persistent compilation
    cache placed, where, and can this process write there. The one
    reader of ``JAX_COMPILATION_CACHE_DIR`` and ``jax.config.
    jax_compilation_cache_dir`` in the package: the W112 lint, the
    warm-ahead gates (resume, elastic shrink) and the flight recorder
    all ask here. jax-free: once jax is imported its config is the
    answer (jax read the variable at import, a later ``jax.config.
    update`` wins, and ``jax_enable_compilation_cache=False`` means no
    cache); before that, the variable jax will read. Touches nothing:
    writability is that of the directory, or of its nearest existing
    ancestor (JAX creates the directory at its first write); a URL
    (``gs://...``) is taken on trust."""
    config = getattr(sys.modules.get("jax"), "config", None)
    if config is None:
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    elif config.jax_enable_compilation_cache:
        d = config.jax_compilation_cache_dir
    else:
        d = None
    if not d:
        return None, False
    if "://" in d:
        return d, True
    at = os.path.abspath(d)
    while not os.path.exists(at) and os.path.dirname(at) != at:
        at = os.path.dirname(at)
    return d, os.path.isdir(at) and os.access(at, os.W_OK | os.X_OK)


class NumericsPanicError(ArithmeticError):
    """Raised by NAN_PANIC/INF_PANIC debug modes (ref: OpExecutioner
    ProfilingMode.NAN_PANIC / INF_PANIC)."""


def panic_check(value, context: str = "loss"):
    """Debug-mode numerics gate: under ``ProfilingMode.NAN_PANIC`` /
    ``INF_PANIC`` (set via ``profiler.set_profiling_mode`` or the
    ``DL4J_TPU_{NAN,INF}_PANIC`` env knobs — one unified mode, ref:
    OpExecutioner.ProfilingMode), synchronously pull ``value`` and raise
    on NaN/Inf with the training context. Costs a host sync per call — a
    DEBUG mode, off by default."""
    from deeplearning4j_tpu.profiler.modes import (ProfilingMode,
                                                   get_profiling_mode)
    # the unified mode is the single gate: an explicit
    # set_profiling_mode(...) override wins over the env knobs
    mode = get_profiling_mode()
    check_nan = mode is ProfilingMode.NAN_PANIC
    check_inf = mode is ProfilingMode.INF_PANIC
    if not (check_nan or check_inf):
        return
    import numpy as _np
    v = _np.asarray(value)
    if check_nan and _np.isnan(v).any():
        raise NumericsPanicError(f"NAN_PANIC: NaN detected in {context}")
    if check_inf and _np.isinf(v).any():
        raise NumericsPanicError(f"INF_PANIC: Inf detected in {context}")
