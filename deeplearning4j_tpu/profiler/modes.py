"""ProfilingMode — unified op-execution profiling levels.

Reference parity: ``org.nd4j.linalg.api.ops.executioner.OpExecutioner
.ProfilingMode`` (OFF / BASIC / NAN_PANIC / INF_PANIC — SURVEY.md §5).
The seed scattered this across two independent Environment booleans
(``nan_panic``/``inf_panic``) plus a ``profiling`` flag; this module is
the single source of truth the op dispatcher, the fit loops, and
``environment.panic_check`` all consult.

Resolution order: an explicit ``set_profiling_mode(...)`` override wins;
otherwise the mode is derived from the Environment knobs on every read
(so ``DL4J_TPU_NAN_PANIC=1`` + ``Environment.reset()`` in tests behaves
exactly as before this module existed).
"""

from __future__ import annotations

import enum
from typing import Optional


class ProfilingMode(enum.Enum):
    OFF = "off"            # no per-op instrumentation
    BASIC = "basic"        # per-op dispatch timing + counters
    NAN_PANIC = "nan_panic"  # BASIC + raise on NaN in op outputs/loss
    INF_PANIC = "inf_panic"  # BASIC + raise on Inf in op outputs/loss


_OVERRIDE: Optional[ProfilingMode] = None

def switched() -> None:
    """The mode or the tracer's flag was set: start or end what lives
    with instrumentation (the ``host:gc`` span, the flush of pending
    step-program maps)."""
    from deeplearning4j_tpu.profiler import _on_switch
    _on_switch()


def set_profiling_mode(mode: Optional[ProfilingMode]) -> None:
    """Set the process-wide mode; ``None`` reverts to Environment-derived.
    Leaving a non-OFF mode builds the step-program maps still pending
    (:mod:`profiler.stepprogram`)."""
    global _OVERRIDE
    if mode is not None and not isinstance(mode, ProfilingMode):
        mode = ProfilingMode(str(mode).lower())
    _OVERRIDE = mode
    switched()


def get_profiling_mode() -> ProfilingMode:
    if _OVERRIDE is not None:
        return _OVERRIDE
    from deeplearning4j_tpu.utils.environment import Environment
    # lock-free fast path: this sits on every eager dispatch, and the
    # singleton is immutable-in-place except via reset() (which swaps the
    # instance — worst case we read the old one for one call)
    env = Environment._instance
    if env is None:
        env = Environment.get()
    if env.nan_panic:
        return ProfilingMode.NAN_PANIC
    if env.inf_panic:
        return ProfilingMode.INF_PANIC
    if env.profiling:
        return ProfilingMode.BASIC
    return ProfilingMode.OFF
