"""``tune/`` — a TVM-style autotuner over the optimization seams.

The three pieces (ISSUE 17):

- :mod:`~deeplearning4j_tpu.tune.space` — :class:`TuningSpace`
  enumerates candidate :class:`TuningPlan`\\ s over the existing seams
  (conv compute layout, fused epilogues, megastep K, precision policy,
  prefetch depth, serving bucket ladder, sharding variants), each plan
  reduced to a stable signature.
- :mod:`~deeplearning4j_tpu.tune.driver` — :func:`tune` searches the
  space on live hardware (random + successive halving + offender-seeded
  greedy refinement; min-of-reps trials through ``CachedDispatch``; a
  loss-parity gate on the winner; with ``cost_spec=`` the
  :mod:`analysis.cost` model statically prunes dominated candidates —
  predicted OOM or step time far beyond the default plan — before any
  measurement is spent, recording each prune's reason on the
  :class:`TuningReport`).
- :mod:`~deeplearning4j_tpu.tune.records` — the persistent
  :class:`TuningRecord` store, keyed by (model fingerprint x mesh x
  backend x jax version), consulted by
  ``fit(tune="auto")``, ``warmup(tuned=True)``, and the serving
  registry.

CLI: ``python -m deeplearning4j_tpu.tune <zoo-model> --budget N``.
"""

from deeplearning4j_tpu.tune.space import (AXES, K_CHOICES, TuningPlan,
                                           TuningSpace, axis_priority)
from deeplearning4j_tpu.tune.driver import (Trial, TuneResult,
                                            TuningReport, estimate_mfu,
                                            loss_parity, tune)
from deeplearning4j_tpu.tune.records import (TuningRecord, auto_apply,
                                             best_plan, configure, lookup,
                                             mesh_signature, put,
                                             record_key,
                                             reset_configuration)

__all__ = [
    "AXES", "K_CHOICES", "TuningPlan", "TuningSpace", "axis_priority",
    "Trial", "TuneResult", "TuningReport", "estimate_mfu", "loss_parity",
    "tune",
    "TuningRecord", "auto_apply", "best_plan", "configure", "lookup",
    "mesh_signature", "put", "record_key", "reset_configuration",
]
