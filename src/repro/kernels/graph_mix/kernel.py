"""Pallas TPU kernel for the paper's neighbor-mixing contraction.

The hot operation of every update in the paper is

    Theta_out[i, :] = sum_k mu[k, i] * Theta[k, :]        (eq. (3)/(7)/(9))

applied to the stacked per-task parameter block Theta (m, d) with the mixing
matrix mu (m, m). On a pod, d is the flattened personalization adapter
(10^5..10^7 floats) and m is the task count (16..256): a skinny matmul whose
roofline is pure HBM bandwidth (arithmetic intensity ~ m/2 flops/byte).

Kernel layout:
  grid over d-tiles; per step load Theta (m, BLK_D) and the whole mu (m, m)
  into VMEM, one (m x m) x (m x BLK_D) MXU contraction, write (m, BLK_D).
  BLK_D is 128-aligned for lane alignment; m is padded to 8 (sublane) by the
  wrapper. mu stays resident across grid steps (constant index_map).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

DEFAULT_BLOCK_D = 512


def _graph_mix_kernel(mu_ref, theta_ref, out_ref):
    mu = mu_ref[...]  # (m, m): mu[k, i]
    theta = theta_ref[...].astype(jnp.float32)  # (m, BLK_D)
    # out[i, :] = sum_k mu[k, i] theta[k, :]  ==  mu^T @ theta. HIGHEST: at
    # the default precision the MXU takes f32 operands as one bf16 pass
    # (2e-3 relative error on a v5e), and the mixing is iterated.
    out_ref[...] = jax.lax.dot_general(
        mu, theta, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def graph_mix_pallas(
    mu: jax.Array,
    theta: jax.Array,
    *,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
) -> jax.Array:
    """mu: (m, m) float32; theta: (m, d). Returns mu^T @ theta, theta.dtype.

    d is padded to a multiple of block_d; m padded to a multiple of 8.
    interpret=None auto-detects: compiled on TPU, interpreter elsewhere.
    """
    interpret = resolve_interpret(interpret)
    m, d = theta.shape
    assert mu.shape == (m, m)
    m_pad = (-m) % 8
    d_pad = (-d) % block_d
    mu_p = jnp.pad(mu.astype(jnp.float32), ((0, m_pad), (0, m_pad)))
    theta_p = jnp.pad(theta, ((0, m_pad), (0, d_pad)))
    mp, dp = theta_p.shape

    out = pl.pallas_call(
        _graph_mix_kernel,
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((mp, mp), lambda j: (0, 0)),  # mu resident in VMEM
            pl.BlockSpec((mp, block_d), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((mp, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((mp, dp), theta.dtype),
        interpret=interpret,
        # the device operation's name in a profile, whatever calls it
        name="graph_mix_pallas",
    )(mu_p, theta_p)
    return out[:m, :d]
