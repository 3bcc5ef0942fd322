"""Pallas TPU kernels of the serving hot path (quantized matmul tiers and
flash-decode), their jitted wrappers (``ops``) and pure-jnp oracles
(``ref``)."""
from jax.experimental.pallas import tpu as pltpu


def compiler_params(*dimension_semantics: str,
                    vmem_limit_bytes: int = None) -> pltpu.CompilerParams:
    """Mosaic compiler params of every ``pallas_call`` in this package:
    one entry per grid axis, ``"parallel"`` or ``"arbitrary"``, and the
    scoped-VMEM limit where a kernel needs more than the default."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit_bytes)
