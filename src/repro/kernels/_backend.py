"""Shared kernel-backend plumbing for the Pallas kernels.

Every kernel in this package takes ``interpret: Optional[bool]`` and used
to copy-paste the same auto-detect: run the compiled Mosaic kernel when
JAX has an accelerator backend (TPU/GPU), fall back to the Pallas
interpreter on CPU-only hosts, where Mosaic lowering is unavailable but
the interpreter executes the identical program.  :func:`resolve_interpret`
is that logic in one place, so a new kernel (or a test monkeypatching the
detected backend) has exactly one seam to hit.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax._src import xla_bridge


def default_backend() -> str:
    """The JAX platform kernels run on (``"cpu"``, ``"tpu"``, ``"gpu"``).

    Thin indirection over :func:`jax.default_backend` so tests can
    monkeypatch the detected platform without touching global JAX state.
    """
    return jax.default_backend()


def initialized_platform() -> Optional[str]:
    """The platform this process's JAX backend runs on, or None when no
    backend has been initialized yet — i.e. when this process holds no
    device.  Unlike :func:`default_backend` it never initializes one.
    """
    if not xla_bridge.backends_are_initialized():
        return None
    return jax.default_backend()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret=None`` kernel argument to a concrete bool.

    ``None`` auto-selects: compiled Mosaic when an accelerator backend is
    available, the Pallas interpreter on CPU-only hosts.  An explicit
    ``True``/``False`` is passed through unchanged.
    """
    if interpret is None:
        return default_backend() == "cpu"
    return bool(interpret)
