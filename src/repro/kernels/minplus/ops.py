"""Entry points: the Pallas kernel, compiled by Mosaic when the program is
lowered for a TPU and run by the Pallas interpreter on any other platform,
with the pure-jnp reference for oracle checks."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import minplus_sweep_pallas
from .ref import minplus_ref


def minplus_sweep(rows: jax.Array, d_total: int,
                  init: Optional[jax.Array] = None):
    """Full T-slot DP sweep (:func:`minplus_sweep_pallas`): one kernel
    launch with the carry row in VMEM scratch.  The choice between Mosaic
    and the interpreter is made at lowering time from the target platform,
    so a program compiled for the chip always holds the kernel."""
    def run(interpret):
        return lambda r, i: minplus_sweep_pallas(r, d_total, i,
                                                 interpret=interpret)
    if init is None:
        init = jnp.full((d_total + 1,), jnp.inf, jnp.float32).at[0].set(0.0)
    return jax.lax.platform_dependent(rows, init, tpu=run(False),
                                      default=run(True))


def minplus(row: jax.Array, prev: jax.Array, use_pallas: bool = True):
    """One slot ``new[d] = min_j row[j] + prev[d-j]``; returns (new, argmin)."""
    if not use_pallas:
        return minplus_ref(row, prev)
    cost, arg = minplus_sweep(row[None, :], prev.shape[0] - 1, prev)
    return cost[0], arg[0]
