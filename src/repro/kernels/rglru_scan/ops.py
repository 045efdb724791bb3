"""Public RG-LRU wrapper matching models.rglru's contract."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import should_interpret
from repro.kernels.rglru_scan.kernel import rglru_pallas


@partial(jax.jit, static_argnames=("interpret",))
def _run(x, lam, ga, gx, interpret):
    return rglru_pallas(x, lam, ga, gx, interpret=interpret)


def rglru(x, lam, ga, gx, h0=None, *, interpret: bool | None = None):
    """Same contract as models.rglru.rglru (h0 unsupported -> reference)."""
    B, S, D = x.shape
    # the kernel's grid floors (B, S, D) by its (8, 128, 512) blocks
    if (h0 is not None or S % 8 or D % 128 or B % min(8, B)
            or S % min(128, S) or D % min(512, D)):
        from repro.kernels.rglru_scan.ref import reference_rglru
        return reference_rglru(x, lam, ga, gx, h0)
    return _run(x, lam, ga, gx, should_interpret(interpret))
