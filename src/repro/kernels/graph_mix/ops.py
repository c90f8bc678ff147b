"""Public ops: graph_mix — jit'd wrapper over the Pallas kernel (compiled on
TPU, interpret mode — the real kernel body executed in Python —
elsewhere; see repro.kernels.runtime) — plus graph_mix_tree, the batched
variant over a pytree of stacked per-task leaves (serving adapter stores)."""
import jax
import jax.numpy as jnp

from repro.kernels.graph_mix.kernel import graph_mix_pallas


def graph_mix(mu: jax.Array, theta: jax.Array, *, block_d: int = 512) -> jax.Array:
    """Neighbor-mixing contraction mu^T @ theta for stacked task params.

    mu: (m, m) mixing weights (column i = weights into task i);
    theta: (m, d) stacked parameters.
    """
    return graph_mix_pallas(mu, theta, block_d=block_d)


def graph_mix_tree(mu: jax.Array, tree, *, block_d: int = 512):
    """Mix EVERY leaf of a pytree of stacked per-task parameters in as few
    kernel dispatches as possible (one per distinct leaf dtype).

    Every leaf must be task-leading — shape ``(m, ...)`` with ``m ==
    mu.shape[0]``; trailing dims are arbitrary (low-rank adapter factors,
    per-task head biases, ...). Leaves are flattened to ``(m, d_i)``,
    concatenated along the personalization axis into ONE ``(m, sum d_i)``
    block per dtype, pushed through the skinny-matmul kernel once, then
    split and reshaped back. This is how the serving adapter store
    (``repro.serve.adapters.TaskAdapterStore``) re-mixes all of its leaves
    between ticks without paying one kernel launch per projection.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    m = mu.shape[0]
    for leaf in leaves:
        if leaf.shape[0] != m:
            raise ValueError(
                f"graph_mix_tree: every leaf must be task-leading (m={m}, "
                f"...); got leaf shape {leaf.shape}"
            )
    # one fused contraction per dtype group (concatenation needs a single
    # dtype; adapter stores are typically homogeneous, so this is one call)
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype).name, []).append(i)
    mixed: list = [None] * len(leaves)
    for key, idxs in groups.items():
        flat = [leaves[i].reshape(m, -1) for i in idxs]
        sizes = [f.shape[1] for f in flat]
        block = jnp.concatenate(flat, axis=1) if len(flat) > 1 else flat[0]
        out = graph_mix_pallas(mu, block, block_d=block_d)
        off = 0
        for i, sz in zip(idxs, sizes):
            mixed[i] = out[:, off : off + sz].reshape(leaves[i].shape)
            off += sz
    return jax.tree_util.tree_unflatten(treedef, mixed)
