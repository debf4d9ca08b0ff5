"""The ONE place that decides how a Pallas kernel is compiled.

Every ``pl.pallas_call`` in the package passes ``interpret=
pallas_target.interpret()``, and every "kernel or XLA twin?" routing
question asks ``on_tpu()``.  Both are keyed on the platform of the
devices the program being traced is compiled for:

  * inside a ``compiling_for(devices)`` scope — entered by the
    shard_map kernel layer with its mesh's devices, and by AOT callers
    that lower for devices other than the process's own (a described
    ``v5e:2x2`` topology in tests/test_tpu_compile.py) — it is those
    devices' platform;
  * otherwise it is the process's default backend.

So a TPU target always gets the real Mosaic kernel: no
``pallas_call`` can be reached with ``interpret=True`` when the target
platform is ``tpu``.  Off-TPU the kernels run in Pallas interpret mode,
which is a correctness simulator for tests, never a measurement path.

``FDT_FORCE_PALLAS_INTERPRET=1`` is a TEST-ONLY seam: off-TPU it makes
flash attention take its (interpreted) kernels instead of the XLA
blockwise twin so the CPU suite can exercise them.  It has no effect
when the target is a TPU.
"""

from __future__ import annotations

import contextlib
import contextvars
import os

import jax

_TARGET: contextvars.ContextVar = contextvars.ContextVar(
    "fdt_pallas_target", default=None)


def target_platform() -> str:
    """Platform the program being traced will be compiled for."""
    return _TARGET.get() or jax.default_backend()


def on_tpu() -> bool:
    return target_platform() == "tpu"


def interpret() -> bool:
    """``interpret=`` for every pallas_call: False iff targeting a TPU."""
    return not on_tpu()


def flash_kernels() -> bool:
    """Whether flash attention takes its Pallas kernels (vs the XLA
    blockwise twin): always on a TPU target; off-TPU only under the
    test-only FDT_FORCE_PALLAS_INTERPRET=1 seam."""
    return on_tpu() or os.environ.get("FDT_FORCE_PALLAS_INTERPRET") == "1"


@contextlib.contextmanager
def compiling_for(devices):
    """Trace for ``devices`` (any iterable of jax devices: a mesh's
    ``devices.flat``, a topology description's ``devices``) instead of
    the default backend."""
    token = _TARGET.set(next(iter(devices)).platform)
    try:
        yield
    finally:
        _TARGET.reset(token)
