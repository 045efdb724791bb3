"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

Production topology (TPU v5e pods):
  single-pod:  (data=16, model=16)            = 256 chips
  multi-pod :  (pod=2, data=16, model=16)     = 512 chips
The "pod" axis is the DCN axis: only (optionally int8-compressed) gradient
all-reduce crosses it; params/optimizer are sharded over data (FSDP) and
model (TP) inside a pod.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the sharding rules steer the partitioner with with_sharding_constraint,
    # which needs Auto axes; jax.make_mesh defaults to Explicit ones
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, dp: int = 16,
                         tp: int = 16):
    """256 chips per pod; (dp, tp) reshapes the intra-pod torus mapping
    (a perf knob: e.g. (64, 4) when head counts don't divide 16)."""
    if dp * tp != 256:
        raise ValueError(f"intra-pod mesh must have 256 chips, got {dp}x{tp}")
    shape = (2, dp, tp) if multi_pod else (dp, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has — used by tests and the CPU examples."""
    n = jax.device_count()
    model_axis = max(1, min(model_axis, n))
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def mesh_chips(mesh) -> int:
    return mesh.devices.size


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link (intra-pod)
DCN_BW = 25e9                     # B/s per host (inter-pod, pod axis)
VMEM_BYTES = 128 * 2**20          # ~128 MiB VMEM per chip
HBM_BYTES = 16 * 2**30            # 16 GiB HBM per chip
