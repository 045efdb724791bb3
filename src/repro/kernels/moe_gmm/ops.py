"""Public grouped expert-FFN wrapper matching models.moe's param layout."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import should_interpret
from repro.kernels.moe_gmm.kernel import expert_ffn_pallas

# Mosaic's default scoped-VMEM limit is 16 MiB on v5e; keep the kernel's
# own estimate under it with headroom for compiler temporaries
VMEM_BUDGET = 14 * 2**20


@partial(jax.jit, static_argnames=("act", "interpret", "block_c", "block_f"))
def _run(xe, w1, w3, w2, act, interpret, block_c, block_f):
    return expert_ffn_pallas(xe, w1.astype(xe.dtype),
                             None if w3 is None else w3.astype(xe.dtype),
                             w2.astype(xe.dtype), act=act, block_c=block_c,
                             block_f=block_f, interpret=interpret)


def _pick_block(n: int, preferred: int, direct_max: int):
    """Largest aligned block that tiles n, else n itself when small."""
    if n % preferred == 0:
        return preferred
    if n <= direct_max:
        return n
    for b in (256, 128, 64, 32, 16, 8):
        if n % b == 0:
            return b
    return None


def _vmem_bytes(bc: int, bf: int, d: int, itemsize: int) -> int:
    """Scoped VMEM one grid step holds: the x and y tiles and the three
    weight tiles, each double-buffered, plus the f32 accumulator and the
    f32 hidden tile."""
    tiles = (2 * bc * d + 3 * d * bf) * itemsize
    return 2 * tiles + bc * d * 4 + 2 * bc * bf * 4


def _pick_blocks(C: int, d: int, f: int, itemsize: int):
    """(block_c, block_f) that tile C and f and fit VMEM_BUDGET, shrinking
    block_f first (it only lengthens the sequential f walk), or None."""
    bc = _pick_block(C, 128, 512)
    bf = _pick_block(f, 512, 1024)
    while bc and bf and _vmem_bytes(bc, bf, d, itemsize) > VMEM_BUDGET:
        if bf > 128 and bf % 256 == 0:
            bf //= 2
        elif bc > 8 and bc % 16 == 0:
            bc //= 2
        else:
            return None
    return (bc, bf) if bc and bf else None


def expert_ffn(xe, p, act: str = "swiglu", *, interpret: bool | None = None):
    """xe: (E, C, d); p: {w1: (E,d,f), w3: (E,d,f)?, w2: (E,f,d)}."""
    C, d, f = xe.shape[1], xe.shape[2], p["w1"].shape[-1]
    blocks = _pick_blocks(C, d, f, xe.dtype.itemsize)
    if blocks is None:                      # odd shapes -> reference path
        from repro.kernels.moe_gmm.ref import reference_expert_ffn
        return reference_expert_ffn(xe, p, act)
    return _run(xe, p["w1"], p.get("w3"), p["w2"], act,
                should_interpret(interpret), *blocks)
