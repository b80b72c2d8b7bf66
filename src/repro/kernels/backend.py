"""Backend selection and VMEM sizing for the Pallas kernels in this package.

Kernels compile on TPU and run in interpret mode on every other backend
(CPU CI containers, GPU hosts without Mosaic).  The choice follows
``jax.default_backend()`` alone; a test that wants interpret mode passes
``interpret=True`` explicitly.

Every compiled kernel asks Mosaic for the same scoped-VMEM limit,
:data:`VMEM_LIMIT_BYTES`, and :func:`block_vmem_bytes` is the one model of
what a block costs there, so a kernel's ``vmem_bytes`` estimate and the
limit it compiles under cannot drift apart.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

# Scoped VMEM each kernel may use: half of a TPU v5e core's 128 MiB, the
# rest left to XLA's own fusions and Mosaic's internal scratch.
VMEM_LIMIT_BYTES = 64 * 2**20
# Headroom below the limit for Mosaic's internal scratch and the
# kernel-body temporaries that the block model does not count.
VMEM_RESERVE_BYTES = 2**18


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """``interpret=None`` → interpret everywhere but on a TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def mxu_precision(dtype) -> lax.Precision:
    """f32 operands contract at full f32 precision (the MXU's default
    pass would round them to bf16); 16-bit operands take the native pass."""
    return (lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else lax.Precision.DEFAULT)


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def block_vmem_bytes(shape, dtype) -> int:
    """VMEM bytes of ONE buffer of a block: the last two dims are padded to
    the (sublane, lane) tile — (8, 128) for 32-bit, (16, 128) for 16-bit."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = shape
    sub = 8 * max(4 // itemsize, 1)
    return (math.prod(lead) * -(-rows // sub) * sub * -(-cols // 128) * 128
            * itemsize)


def pipelined_vmem_bytes(blocks, scratch=()) -> int:
    """Scoped VMEM of a ``pallas_call``: every in/out block double-buffered
    by the pipeline, plus its scratch buffers and the fixed reserve."""
    return (2 * sum(block_vmem_bytes(s, d) for s, d in blocks)
            + sum(block_vmem_bytes(s, d) for s, d in scratch)
            + VMEM_RESERVE_BYTES)
