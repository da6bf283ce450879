"""Public op over the bitonic kernel (TPU -> Pallas, else oracle)."""

from ..dispatch import resolve
from . import kernel as _k
from . import ref as _ref


def bitonic_sort(x, *, use_pallas=None, interpret=None):
    use_pallas, interpret = resolve(use_pallas, interpret)
    if use_pallas:
        return _k.sort_pallas(x, interpret=interpret)
    return _ref.sort_ref(x)
