"""The one place that decides how Pallas kernels run: compiled or interpreted.

Every kernel entry point takes ``interpret=None`` and resolves it here, so a
kernel compiles to a Mosaic ``tpu_custom_call`` on a TPU and runs in the
Pallas interpreter on the CPU (tests, examples).  Any other backend is an
error: there is no kernel for it, and guessing would hide the device.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_for(backend: Optional[str] = None) -> bool:
    """Whether Pallas kernels run in the interpreter on ``backend``
    (default ``jax.default_backend()``): True on 'cpu', False on 'tpu'."""
    backend = backend or jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas kernel mode for backend {backend!r}: kernels compile on "
        "'tpu' and are interpreted on 'cpu'")


def resolve(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; None asks the backend."""
    return interpret_for() if interpret is None else interpret
