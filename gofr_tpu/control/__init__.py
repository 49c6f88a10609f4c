"""Online step-level control plane (ROADMAP O2; docs/serving.md § "Online
controller").

PR 14's perf plane made every device step priced and every pipeline gap
accounted; this package promotes that measurement to ACTUATION. Two
pieces:

- :mod:`gofr_tpu.control.hysteresis` — the sustain/idle/cooldown/stale
  decision core extracted from the PR 11 elastic-fleet ``ScaleDecider``
  (fleet/autoscaler.py), shared verbatim between fleet-level replica
  scaling and step-level knob tuning so both planes damp flapping the
  same proven way;
- :mod:`gofr_tpu.control.controller` — the per-engine ``StepController``
  that bucketizes live perf samples per (step kind, kv dtype, occupancy
  band) and proposes bounded single-knob moves for pipeline depth,
  chunked-prefill chunk size, speculative round length and admission
  batch width, judged by measured roofline attainment and ``_dq``
  bubble ratio, with decisions pinned and persisted so a
  restarted fleet resumes tuned.

The thesis is PAPERS.md 1605.08695 applied at the step level: the
system adapts to the workload, not the workload to the system.
"""

from gofr_tpu.control.controller import (
    ControlPolicy,
    Decision,
    KnobSpec,
    StepController,
)
from gofr_tpu.control.hysteresis import HysteresisGate

__all__ = [
    "ControlPolicy",
    "Decision",
    "HysteresisGate",
    "KnobSpec",
    "StepController",
]
