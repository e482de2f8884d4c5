"""JAX/XLA compute kernels for the erasure-code data path."""

from ceph_tpu.ops.gf2 import gf2_apply_bytes, gf2_matmul

__all__ = ["gf2_matmul", "gf2_apply_bytes"]
