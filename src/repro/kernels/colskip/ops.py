"""Public op over the colskip sort kernel (TPU -> Pallas, else oracle)."""

from __future__ import annotations

from ..dispatch import impl_name, resolve
from . import kernel as _k
from . import ref as _ref


def resolve_colskip(use_pallas: bool | None = None,
                    interpret: bool | None = None,
                    packed: bool = True) -> tuple[bool, bool, str]:
    """``(use_pallas, interpret, impl)`` for a colskip call.

    The platform rule of :func:`repro.kernels.dispatch.resolve`, except that
    the dense carrier has no compiled kernel: left to the default, it runs
    on the XLA reference where the kernel would be compiled."""
    if use_pallas is None and not packed and resolve(None, interpret) == (
            True, False):
        use_pallas = False
    use_pallas, interpret = resolve(use_pallas, interpret)
    return use_pallas, interpret, impl_name(use_pallas, interpret)


def colskip_sort_batched(x, w: int = 32, k: int = 2, *,
                         use_pallas: bool | None = None,
                         interpret: bool | None = None,
                         stop_after: int | None = None,
                         packed: bool = True, plane_steps: bool = False):
    """Sort rows of ``x`` (B, N) uint32; returns (values, order, CRs, cycles).

    CR/cycle telemetry is the paper's latency metric (fed to the cost model).
    ``stop_after=k'`` runs the k-early-exit drain: each row stops after its
    first ``k'`` minima, outputs are (B, k'), and the per-row cycle counts
    cover only the executed iterations (the k-min serving mode).
    ``packed=False`` selects the dense-boolean machine (equivalence
    baseline) instead of the lane-packed hot path.
    ``plane_steps=True`` (the kernel only) also returns the plane steps the
    kernel walked, as a second column of the CRs (see ``sort_pallas``).
    """
    use_pallas, interpret, _ = resolve_colskip(use_pallas, interpret, packed)
    if use_pallas:
        return _k.sort_pallas(x, w, k, interpret=interpret,
                              stop_after=stop_after, packed=packed,
                              plane_steps=plane_steps)
    if plane_steps:
        raise ValueError("only the Pallas kernel counts plane steps")
    return _ref.sort_ref(x, w, k, stop_after=stop_after, packed=packed)
