"""Kernel dispatch, resolved once from the platform.

On a TPU every Pallas kernel runs compiled (Mosaic).  Elsewhere the ops use
their XLA reference, and a Pallas kernel the caller asks for runs in
interpret mode.  An explicit ``interpret=True`` is always honoured (the CPU
tests use it); ``None`` never becomes ``True`` on a TPU.
"""

from __future__ import annotations

import jax


def resolve(use_pallas: bool | None = None,
            interpret: bool | None = None) -> tuple[bool, bool]:
    """``(use_pallas, interpret)`` with every ``None`` filled in.

    On TPU: ``(True, False)``.  Off TPU: ``(False, True)`` — the XLA
    reference, and interpret mode for a Pallas call made anyway.  Passing
    ``interpret`` alone also selects Pallas, as it always has."""
    tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        use_pallas = tpu or bool(interpret)
    if interpret is None:
        interpret = not tpu
    return bool(use_pallas), bool(interpret)


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret flag for a Pallas call that is made either way."""
    return resolve(True, interpret)[1]


def impl_name(use_pallas: bool, interpret: bool) -> str:
    """What actually runs: ``"pallas"`` (compiled), ``"interpret"`` or
    ``"xla"`` (the reference path)."""
    if not use_pallas:
        return "xla"
    return "interpret" if interpret else "pallas"
