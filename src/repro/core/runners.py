"""shard_map execution of the paper's algorithms: the actual distributed
program, one task per device along a named mesh axis.

``bol_sharded`` / ``bsr_sharded`` are bit-for-bit the math of
`repro.core.algorithms.bol/bsr` but with every cross-task contraction
expressed as an explicit collective:

  * BOL: iterate mixing via ``mix_ring`` (collective_permute hops — band
    graphs only) or ``mix_all_gather`` (any graph), then a purely LOCAL prox.
  * BSR: per-machine gradients all-gathered and contracted with this
    device's column of M^{-1}.

Tested against the single-device implementations in
tests/test_distributed_runners.py (subprocess with forced host devices).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.distributed import mix_all_gather, mix_ring, mixing_spec_for_band_graph
from repro.core.objective import MultiTaskProblem

Array = jax.Array


def _local_prox_squared(v, x, y, alpha):
    """Per-device prox (one task): v (1, d), x (1, n, d), y (1, n)."""
    n = x.shape[1]
    d = v.shape[-1]
    a_mat = jnp.eye(d) / alpha + (2.0 / n) * x[0].T @ x[0]
    b = v[0] / alpha + (2.0 / n) * x[0].T @ y[0]
    return jnp.linalg.solve(a_mat, b)[None]


def bol_sharded(
    problem: MultiTaskProblem,
    x: Array,
    y: Array,
    num_iters: int,
    mesh,
    axis_name: str = "task",
    stepsize: float | None = None,
    use_ring: bool | None = None,
):
    """Distributed BOL: tasks sharded one-per-device over ``axis_name``.

    Communication per iteration: ONE neighbor exchange (ring) or one
    all-gather of the iterate — exactly the paper's Table-1 BOL row.
    """
    if problem.loss.name != "squared":
        raise NotImplementedError("sharded BOL implemented for squared loss")
    m, n, d = x.shape
    eta, tau = problem.eta, problem.tau
    alpha = stepsize if stepsize is not None else 1.0 / (
        eta + tau * problem.graph.lambda_max
    )
    band = mixing_spec_for_band_graph(problem.graph, eta, tau, alpha)
    if use_ring is None:
        use_ring = band is not None
    mu = jnp.asarray(problem.graph.bol_mixing(eta, tau, alpha), jnp.float32)

    def local_step(w_loc, x_loc, y_loc, mu_col):
        # w_loc (1, d): this device's task iterate
        if use_ring:
            self_w, nbr = band
            mixed = mix_ring(w_loc, self_w, nbr, axis_name, m)
        else:
            mixed = mix_all_gather(w_loc, mu_col[:, 0], axis_name)
        return _local_prox_squared(mixed, x_loc, y_loc, alpha)

    def run(w0, xs, ys, mu_mat):
        def body(w, _):
            w = jax.shard_map(
                local_step,
                mesh=mesh,
                in_specs=(
                    P(axis_name, None),
                    P(axis_name, None, None),
                    P(axis_name, None),
                    P(None, axis_name),
                ),
                out_specs=P(axis_name, None),
            )(w, xs, ys, mu_mat)
            return w, None

        w, _ = jax.lax.scan(body, w0, None, length=num_iters)
        return w

    w0 = jnp.zeros((m, d), jnp.float32)
    return jax.jit(run)(w0, x, y, mu)


def bsr_sharded(
    problem: MultiTaskProblem,
    x: Array,
    y: Array,
    num_iters: int,
    mesh,
    axis_name: str = "task",
    stepsize: float | None = None,
):
    """Distributed BSR: per-machine GRADIENTS are all-gathered (the paper's
    broadcast channel) and contracted with this device's M^{-1} column."""
    if problem.loss.name != "squared":
        raise NotImplementedError("sharded BSR implemented for squared loss")
    m, n, d = x.shape
    eta, tau = problem.eta, problem.tau
    beta_f = problem.smoothness_loss(x)
    alpha = stepsize if stepsize is not None else 1.0 / (beta_f + eta)
    m_inv = jnp.asarray(problem.graph.metric_inverse(eta, tau), jnp.float32)

    def local_step(w_loc, x_loc, y_loc, minv_col):
        # local gradient of F_hat_i (per-machine convention)
        grad = (2.0 / n) * jnp.einsum(
            "nd,n->d", x_loc[0], x_loc[0] @ w_loc[0] - y_loc[0]
        )[None]
        mixed_grad = mix_all_gather(grad, minv_col[:, 0], axis_name)
        return (1.0 - alpha * eta) * w_loc - alpha * mixed_grad

    def run(w0, xs, ys, minv):
        def body(w, _):
            w = jax.shard_map(
                local_step,
                mesh=mesh,
                in_specs=(
                    P(axis_name, None),
                    P(axis_name, None, None),
                    P(axis_name, None),
                    P(None, axis_name),
                ),
                out_specs=P(axis_name, None),
            )(w, xs, ys, minv)
            return w, None

        w, _ = jax.lax.scan(body, w0, None, length=num_iters)
        return w

    w0 = jnp.zeros((m, d), jnp.float32)
    return jax.jit(run)(w0, x, y, m_inv)
