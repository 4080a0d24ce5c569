"""Kernel-backend registry and dispatch.

The paper's premise is commodity hardware: the same model code must run
on a TPU pod, a single GTX-class GPU, or a laptop CPU.  Each custom op
(`flash_attention`, `decode_attention`, `rmsnorm`, `ssm_scan`,
`slstm_scan`, `segment_tree`, `categorical_projection`) therefore has
up to four executable backends (see docs/kernel_backends.md for the
registry contract and a how-to for authoring the next op):

  ==========  ============================================================
  backend     what runs
  ==========  ============================================================
  mosaic      Pallas lowered through TPU Mosaic (TPU hosts)
  triton      Pallas lowered through GPU Triton (CUDA/ROCm hosts)
  interpret   the Pallas kernel in interpreter mode (any host; validation)
  ref         the pure-XLA oracle in ``kernels/ref.py`` (any host)
  ==========  ============================================================

Selection is resolved **at trace time** from three inputs, in decreasing
precedence:

  1. the ``REPRO_KERNEL_BACKEND`` environment variable (operator
     override — "force `ref` on my laptop");
  2. the request threaded from ``ExecConfig.kernel_backend``;
  3. ``auto``: TPU -> mosaic, GPU -> triton, CPU -> ref.

Logical requests (``auto`` / ``pallas``) map to a concrete backend via
``jax.default_backend()``; a concrete backend with no registered
implementation for an op falls back to ``ref`` (e.g. the sequential
``slstm_scan`` has no Triton lowering). On a TPU both DQN ops
(``segment_tree``, ``categorical_projection``) resolve to ``mosaic``;
``chip_smoke.py`` fails if they do not.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas import triton as pltriton

MOSAIC = "mosaic"
TRITON = "triton"
INTERPRET = "interpret"
REF = "ref"
CONCRETE_BACKENDS = (MOSAIC, TRITON, INTERPRET, REF)

AUTO = "auto"
PALLAS = "pallas"
REQUESTS = (AUTO, PALLAS) + CONCRETE_BACKENDS

ENV_VAR = "REPRO_KERNEL_BACKEND"

OPS = ("flash_attention", "decode_attention", "rmsnorm", "ssm_scan",
       "slstm_scan", "segment_tree", "categorical_projection")

_REGISTRY: Dict[str, Dict[str, Callable]] = {}


def register(op: str, backend: str):
    """Decorator registering ``fn`` as the ``backend`` impl of ``op``."""
    assert op in OPS, op
    assert backend in (MOSAIC, TRITON, INTERPRET), backend

    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(op, {})[backend] = fn
        return fn

    return deco


def registered(op: str) -> Tuple[str, ...]:
    """Concrete backends with an implementation for ``op`` (ref always)."""
    reg = _REGISTRY.get(op, {})
    out = [b for b in (MOSAIC, TRITON) if b in reg]
    if INTERPRET in reg or out:
        out.append(INTERPRET)
    out.append(REF)
    return tuple(out)


def platform() -> str:
    """Normalized accelerator platform: 'tpu' | 'gpu' | 'cpu'."""
    p = jax.default_backend()
    if p in ("cuda", "rocm"):
        return "gpu"
    return p if p in ("tpu", "gpu") else "cpu"


def resolve(request: Optional[str] = None,
            plat: Optional[str] = None) -> str:
    """Resolve a logical request to a concrete backend for this host.

    ``request=None`` means "no preference" (-> env var, then auto).
    ``plat`` overrides platform detection (tests).
    """
    env = os.environ.get(ENV_VAR, "").strip().lower()
    req = env or (request or AUTO).strip().lower()
    if req not in REQUESTS:
        raise ValueError(
            f"unknown kernel backend {req!r}; expected one of {REQUESTS}")
    p = plat or platform()
    if req == AUTO:
        return {"tpu": MOSAIC, "gpu": TRITON}.get(p, REF)
    if req == PALLAS:
        return {"tpu": MOSAIC, "gpu": TRITON}.get(p, INTERPRET)
    return req


def choose(op: str, request: Optional[str] = None,
           plat: Optional[str] = None) -> str:
    """Concrete backend that will actually run ``op`` on this host."""
    assert op in OPS, op
    b = resolve(request, plat)
    if b == REF:
        return REF
    reg = _REGISTRY.get(op, {})
    if b == INTERPRET:
        return INTERPRET if (INTERPRET in reg or MOSAIC in reg
                             or TRITON in reg) else REF
    return b if b in reg else REF


def lookup(op: str, backend: str) -> Callable:
    """The callable implementing ``op`` on a concrete non-ref backend.

    Returned callables share the registered kernel signature (kernel
    layout, op-specific kwargs); interpret-mode partials are built here
    so call sites never pass ``interpret=`` themselves.
    """
    reg = _REGISTRY.get(op, {})
    if backend == INTERPRET:
        if INTERPRET in reg:
            return functools.partial(reg[INTERPRET], interpret=True)
        impl = reg.get(MOSAIC) or reg.get(TRITON)
        if impl is None:
            raise KeyError(f"no interpretable kernel for {op}")
        return functools.partial(impl, interpret=True)
    if backend not in reg:
        raise KeyError(f"{op} has no {backend} implementation; "
                       f"registered: {registered(op)}")
    return reg[backend]


def testable_backends(op: str) -> Tuple[str, ...]:
    """Backends exercisable on *this* host (for CI parametrization).

    ``mosaic``/``triton`` compile only on their native platform; the
    interpreter and the XLA ref run anywhere.
    """
    p = platform()
    out = []
    for b in registered(op):
        if b == MOSAIC and p != "tpu":
            continue
        if b == TRITON and p != "gpu":
            continue
        out.append(b)
    return tuple(out)


def compiler_params(backend: str, *, interpret: bool = False,
                    dimension_semantics: Optional[tuple] = None,
                    num_warps: Optional[int] = None,
                    num_stages: Optional[int] = None) -> Any:
    """Pallas compiler params for ``backend`` ('mosaic' | 'triton'), or
    ``None`` in interpret mode (the interpreter ignores them).

    TPU-only knobs (``dimension_semantics``) and GPU-only knobs
    (``num_warps`` / ``num_stages``) are filtered to the matching backend,
    so a kernel shared by both schedules can declare both.
    """
    if interpret:
        return None
    if backend == MOSAIC:
        return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
    if backend == TRITON:
        kw = {k: v for k, v in (("num_warps", num_warps),
                                ("num_stages", num_stages)) if v is not None}
        return pltriton.CompilerParams(**kw)
    raise ValueError(f"no compiler params for backend {backend!r}")
