"""Pallas TPU kernels for the store's clock algebra.

The kernel ships as dvv_ops/dvv_ops.py (pl.pallas_call + BlockSpec),
dvv_ops/ops.py (jit'd public wrapper, interpret-mode fallback off-TPU) and
dvv_ops/ref.py (pure-jnp oracle used by the sweep tests):

  * dvv_ops — batched dotted-version-vector dominance (the paper's clock
              algebra, vectorized for anti-entropy and read sweeps)
"""
