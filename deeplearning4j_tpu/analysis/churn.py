"""Recompile-churn detector — the runtime half of the linter.

Every distinct (shape, dtype, weak-type) signature a dispatch site sees
costs one full XLA compile. A training loop whose batch shapes drift
(ragged final batches, per-epoch bucketing, weak-typed python scalars
promoted differently between calls) silently recompiles over and over —
on a real TPU each recompile is seconds of wall clock and the symptom is
just "training is slow".

The networks' ``_fit_one``/``_fit_mega`` paths and the native runtime's
compile cache report fingerprints here; the detector counts distinct
signatures per site into the process-wide profiler registry
(``dl4j_recompiles_total{site=...}``) and emits a ``DL4J-W201``
diagnostic (plus one python warning) the first time a site crosses the
threshold. ``model.validate()`` folds any findings for that model into
its report.

No jax imports — fingerprints are built from duck-typed ``.shape`` /
``.dtype`` / ``.weak_type`` attributes so the module stays pure-static.
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Dict, List, Optional, Set, Tuple

from deeplearning4j_tpu.analysis.diagnostics import Diagnostic, Severity

def _default_threshold() -> int:
    """Read at detector construction (NOT module import — the package is
    imported as a side effect of importing any network class, long before
    a script gets the chance to set the knob)."""
    return int(os.environ.get("DL4J_TPU_RECOMPILE_CHURN_THRESHOLD", "8"))


def array_fingerprint(*arrays) -> Tuple:
    """Jit-cache-equivalent signature of a positional argument list:
    (shape, dtype, weak_type) per array, None passed through. Two calls
    with equal fingerprints hit the same compiled program; a new
    fingerprint is a recompile."""
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
        elif isinstance(a, (list, tuple)):
            out.append(array_fingerprint(*a))
        else:
            out.append((tuple(getattr(a, "shape", ())),
                        str(getattr(a, "dtype", type(a).__name__)),
                        bool(getattr(a, "weak_type", False))))
    return tuple(out)


class RecompileChurnDetector:
    """Counts distinct jit signatures per dispatch site.

    ``record(site, fingerprint, owner=...)`` is the hot-path call: one
    lock + set lookup when the signature was already seen. ``owner``
    scopes the threshold bookkeeping (two models sharing a site string
    do not pool their signatures); the metrics label stays the coarse
    ``site`` name.
    """

    def __init__(self, threshold: int = None, registry=None):
        from deeplearning4j_tpu.profiler.metrics import get_registry
        self.threshold = _default_threshold() if threshold is None \
            else int(threshold)
        self._counter = (registry or get_registry()).counter(
            "dl4j_recompiles_total",
            "Distinct jit signatures compiled per dispatch site (a value "
            "that keeps growing during steady-state training is churn)",
            labelnames=("site",))
        # instrumented (PR-8 adoption sweep): record() sits on every fit
        # dispatch — the lock itself is only taken per NEW signature, but
        # contention here is exactly the churn the detector exists to see
        from deeplearning4j_tpu.profiler.locks import InstrumentedLock
        self._lock = InstrumentedLock("churn_detector")
        self._seen: Dict[Tuple[str, int], Set] = {}
        self._flagged: Set[Tuple[str, int]] = set()
        self._diags: List[Tuple[Optional[int], Diagnostic]] = []

    def record(self, site: str, fingerprint, owner=None) -> bool:
        """Report one dispatch signature; returns whether it was new to
        ``site`` (scoped to ``owner``): a first sight, which a jit pays
        for with a build. The first time a site crosses the threshold its
        W201 diagnostic is kept for :meth:`diagnostics_for` and warned."""
        key = (site, id(owner) if owner is not None else 0)
        # lock-free fast path for the per-iteration hot loop: a GIL-safe
        # dict/set read suffices once the signature has been seen (the
        # steady-state case — the lock is only taken per NEW signature)
        seen = self._seen.get(key)
        if seen is not None and fingerprint in seen:
            return False
        with self._lock:
            seen = self._seen.get(key)
            if seen is None:
                seen = self._seen[key] = set()
            if fingerprint in seen:
                return False
            seen.add(fingerprint)
            n = len(seen)
            crossed = n > self.threshold and key not in self._flagged
            if crossed:
                self._flagged.add(key)
        self._counter.labels(site=site).inc()
        if not crossed:
            return True
        diag = Diagnostic(
            "DL4J-W201", Severity.WARNING, site,
            f"{n} distinct jit signatures compiled at this site "
            f"(threshold {self.threshold}) — shifting batch shapes/dtypes "
            f"are forcing repeated XLA recompiles",
            fix_hint="pad or bucket batches to a fixed shape (e.g. drop/pad "
                     "the ragged final batch), pin input dtypes, and avoid "
                     "weak-typed python scalars in the step inputs")
        with self._lock:
            self._diags.append((key[1] or None, diag))
        warnings.warn(f"{diag.code} [{site}]: {diag.message}",
                      RuntimeWarning, stacklevel=2)
        return True

    def signature_count(self, site: str, owner=None) -> int:
        key = (site, id(owner) if owner is not None else 0)
        with self._lock:
            return len(self._seen.get(key, ()))

    def diagnostics_for(self, owner=None) -> List[Diagnostic]:
        """Findings scoped to ``owner`` (plus unscoped sites like the
        native compile cache when ``owner`` is None)."""
        oid = None if owner is None else id(owner)
        with self._lock:
            return [d for o, d in self._diags
                    if o == oid or (owner is not None and o is None)]

    def reset(self) -> None:
        with self._lock:
            self._seen.clear()
            self._flagged.clear()
            self._diags.clear()


_DETECTOR: Optional[RecompileChurnDetector] = None
_DETECTOR_LOCK = threading.Lock()


def get_churn_detector() -> RecompileChurnDetector:
    """Process-wide detector the dispatch seams report into."""
    global _DETECTOR
    if _DETECTOR is None:
        with _DETECTOR_LOCK:
            if _DETECTOR is None:
                _DETECTOR = RecompileChurnDetector()
    return _DETECTOR
