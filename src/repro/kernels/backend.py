"""Backend selection for the Pallas kernels.

Every kernel wrapper takes ``interpret: bool | None``.  ``None`` (the
default everywhere) resolves via :func:`resolve_interpret`: compiled on a
TPU, interpreter mode on the CPU (the correctness twin the tests run).  Any
other backend raises: the kernels are written for the TPU, and quietly
interpreting them on an accelerator would serve at interpreter speed.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` flag: explicit values win; ``None`` means
    compiled on ``tpu``, interpreted on ``cpu``, and an error elsewhere."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for 'tpu' and interpret on 'cpu'; "
        f"backend {backend!r} is neither (pass interpret=True explicitly "
        f"to run the interpreter there)"
    )
