"""Custom-kernel package: per-backend implementations + dispatch.

Layout:
  backend.py  — kernel registry and trace-time backend resolution
                (mosaic | triton | interpret | ref), env override via
                ``REPRO_KERNEL_BACKEND``; per-backend Pallas compiler
                params.
  ref.py      — pure-XLA oracles; the semantics contract for every op
                and the always-available fallback backend.
  ops.py      — public model-facing wrappers (layout transposes,
                head-dim padding, custom-vjp recompute, dispatch).
  <op>.py     — the Pallas kernels themselves (TPU Mosaic schedules
                plus GPU-Triton schedules where the op parallelizes).

Importing ``ops`` (done here) pulls in every kernel module, which
registers its implementations with the backend registry as a side
effect.
"""

from repro.kernels import backend  # noqa: F401
from repro.kernels import ops  # noqa: F401  (populates the registry)
