"""Kernels: XLA variants (xla.py), Pallas TPU kernels (*_pallas.py) and
their pure-jnp oracles (ref.py)."""
import jax

# Every contraction in every kernel module runs at full f32. On a TPU the
# default precision rounds f32 operands to bf16: a Pallas block-ELL SpMM
# then missed an f32 accumulation bound by 5e4x on a TPU v5e, and a
# candidate would compute a different function than the baseline it is
# probed against. On a TPU, HIGHEST forms each f32 product from six bf16
# passes (`jax.lax.Precision` docstring; Mosaic lowers it to
# `#tpu.contract_precision<fp32>`).
F32_MXU = jax.lax.Precision.HIGHEST
