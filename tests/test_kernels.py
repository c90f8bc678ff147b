"""Pallas kernel validation: shape/dtype sweeps + hypothesis property tests,
always against the pure-jnp ref.py oracles (interpret mode executes the real
kernel bodies on CPU).

Only the property tests need hypothesis (requirements-dev.txt); the
parametrized oracle sweeps are tier-1 and run everywhere — a module-level
importorskip used to silently drop ALL kernel coverage on machines without
hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # property tests skip; oracle sweeps still run
    HAVE_HYPOTHESIS = False

from repro.core import band_graph
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.graph_mix.kernel import graph_mix_pallas
from repro.kernels.graph_mix.ref import graph_mix_reference
from repro.kernels.prefill_attention.kernel import (
    paged_prefill_attention_pallas,
    prefill_attention_pallas,
)
from repro.kernels.prefill_attention.ref import (
    paged_prefill_attention_reference,
    prefill_attention_reference,
)


# ------------------------------------------------------------- graph_mix
@pytest.mark.parametrize("m", [4, 16, 32, 100])
@pytest.mark.parametrize("d", [128, 512, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_graph_mix_shapes_dtypes(m, d, dtype):
    rng = np.random.default_rng(0)
    mu = jnp.asarray(rng.standard_normal((m, m)), jnp.float32)
    theta = jnp.asarray(rng.standard_normal((m, d))).astype(dtype)
    got = graph_mix_pallas(mu, theta, block_d=256, interpret=True)
    want = graph_mix_reference(mu, theta)
    assert got.dtype == theta.dtype and got.shape == theta.shape
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_graph_mix_matches_paper_update():
    """The kernel applied with mu = I - a*eta*M is exactly the BOL mixing."""
    g = band_graph(16, 2)
    eta, tau, alpha = 0.5, 2.0, 0.05
    mu = jnp.asarray(g.bol_mixing(eta, tau, alpha), jnp.float32)
    rng = np.random.default_rng(1)
    theta = jnp.asarray(rng.standard_normal((16, 384)), jnp.float32)
    got = graph_mix_pallas(mu, theta, interpret=True)
    want = jnp.asarray(mu).T @ theta
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=25)
    @given(
        m=st.integers(2, 24),
        d=st.integers(1, 300),
        block=st.sampled_from([128, 256]),
        seed=st.integers(0, 10_000),
    )
    def test_graph_mix_property(m, d, block, seed):
        rng = np.random.default_rng(seed)
        mu = jnp.asarray(rng.standard_normal((m, m)), jnp.float32)
        theta = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        got = graph_mix_pallas(mu, theta, block_d=block, interpret=True)
        want = graph_mix_reference(mu, theta)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4
        )


def test_graph_mix_row_stochastic_preserves_constants():
    """Property: doubly-stochastic mixing leaves a constant stack invariant."""
    g = band_graph(12, 1)
    mu = jnp.asarray(g.consensus_mixing(), jnp.float32)
    theta = jnp.full((12, 200), 3.25, jnp.float32)
    got = graph_mix_pallas(mu, theta, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 3.25, rtol=1e-5)


# --------------------------------------- graph_mix vs TaskGraph mixing families
def _mixing_cases(m=12):
    """The three mixing families on a band graph + the complete graph."""
    from repro.core import complete_graph

    band, comp = band_graph(m, 2), complete_graph(m)
    return {
        "bsr": band.bsr_mixing(eta=0.5, tau=2.0, alpha=1.0),
        "bol": band.bol_mixing(eta=0.5, tau=2.0, alpha=0.05),
        "consensus": band.consensus_mixing(),
        "consensus_complete": comp.consensus_mixing(),
    }


def test_mixing_matrix_row_sums():
    """Structural properties the serving store relies on: bsr(alpha=1) rows
    sum to 1 (M^-1 of a matrix with unit row sums), bol rows sum to
    1 - alpha*eta, consensus is doubly stochastic (symmetric, unit rows)."""
    cases = _mixing_cases()
    np.testing.assert_allclose(cases["bsr"].sum(axis=1), 1.0, atol=1e-8)
    np.testing.assert_allclose(
        cases["bol"].sum(axis=1), 1.0 - 0.05 * 0.5, atol=1e-8
    )
    for key in ("consensus", "consensus_complete"):
        mu = cases[key]
        np.testing.assert_allclose(mu.sum(axis=1), 1.0, atol=1e-8)
        np.testing.assert_allclose(mu, mu.T, atol=1e-12)


@pytest.mark.parametrize("name", ["bsr", "bol", "consensus"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_graph_mix_matches_mixing_families(name, dtype):
    """Kernel parity against the einsum oracle under every REAL mixing
    matrix (not just random mu), in f32 and bf16."""
    mu = jnp.asarray(_mixing_cases()[name], jnp.float32)
    rng = np.random.default_rng(7)
    theta = jnp.asarray(rng.standard_normal((12, 384))).astype(dtype)
    got = graph_mix_pallas(mu, theta, interpret=True)
    want = graph_mix_reference(mu, theta)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_graph_mix_consensus_fixed_point():
    """Doubly-stochastic consensus weights: the uniform average is a fixed
    point, and on the COMPLETE graph one application of ``I - L/lam_max ==
    J/m`` collapses ANY stack straight to that fixed point."""
    cases = _mixing_cases()
    rng = np.random.default_rng(3)
    theta = jnp.asarray(rng.standard_normal((12, 256)), jnp.float32)
    mean = jnp.mean(theta, axis=0, keepdims=True)
    # mean stack is invariant under any doubly-stochastic mixing
    mu = jnp.asarray(cases["consensus"], jnp.float32)
    got = graph_mix_pallas(mu, jnp.broadcast_to(mean, theta.shape),
                           interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.broadcast_to(mean, theta.shape)),
        atol=1e-5,
    )
    # complete graph: one mix == the consensus projection itself
    mu_c = jnp.asarray(cases["consensus_complete"], jnp.float32)
    got_c = graph_mix_pallas(mu_c, theta, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_c), np.asarray(jnp.broadcast_to(mean, theta.shape)),
        atol=1e-5,
    )


def test_graph_mix_tree_matches_leafwise_reference():
    """The batched tree op (dtype-grouped concat -> one kernel call ->
    split/reshape) must equal mixing each leaf independently, across mixed
    dtypes and arbitrary trailing shapes."""
    from repro.kernels import graph_mix_tree, graph_mix_tree_reference

    m = 12
    mu = jnp.asarray(_mixing_cases(m)["bsr"], jnp.float32)
    rng = np.random.default_rng(11)
    tree = {
        "a": jnp.asarray(rng.standard_normal((m, 3, 8, 4)), jnp.float32),
        "b": jnp.asarray(rng.standard_normal((m, 50)), jnp.bfloat16),
        "c": [jnp.asarray(rng.standard_normal((m, 2, 7)), jnp.float32)],
    }
    got = graph_mix_tree(mu, tree)
    want = graph_mix_tree_reference(mu, tree)
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=3e-2, atol=3e-2,
        )
    with pytest.raises(ValueError, match="task-leading"):
        graph_mix_tree(mu, {"bad": jnp.zeros((m + 1, 4))})


# ------------------------------------------------------- decode_attention
@pytest.mark.parametrize("kvh,g", [(1, 4), (2, 8), (8, 1), (4, 4)])
@pytest.mark.parametrize("s,block_s", [(256, 128), (512, 256), (300, 128)])
def test_decode_attention_shapes(kvh, g, s, block_s):
    rng = np.random.default_rng(0)
    b, hd = 2, 64
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    pos = jnp.asarray(s - 5, jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, block_s=block_s, interpret=True)
    want = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_decode_attention_dtypes(dtype, tol):
    rng = np.random.default_rng(1)
    b, s, kvh, g, hd = 2, 384, 2, 4, 128
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd))).astype(dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd))).astype(dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd))).astype(dtype)
    pos = jnp.asarray(200, jnp.int32)
    got = decode_attention_pallas(q, k, v, pos, block_s=128, interpret=True)
    want = decode_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


def test_decode_attention_sliding_window():
    rng = np.random.default_rng(2)
    b, s, kvh, g, hd = 1, 512, 2, 2, 64
    q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    pos = jnp.asarray(400, jnp.int32)
    got = decode_attention_pallas(
        q, k, v, pos, block_s=128, window=128, interpret=True
    )
    want = decode_attention_reference(q, k, v, pos, window=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=20)
    @given(
        s=st.integers(16, 640),
        pos_frac=st.floats(0.0, 1.0),
        kvh=st.sampled_from([1, 2, 4]),
        g=st.sampled_from([1, 2, 6]),
        seed=st.integers(0, 10_000),
    )
    def test_decode_attention_property(s, pos_frac, kvh, g, seed):
        """Invariant: kernel == oracle for any cache length / decode
        position, including pos << S (most of the cache masked)."""
        rng = np.random.default_rng(seed)
        b, hd = 1, 64
        pos = jnp.asarray(int(pos_frac * (s - 1)), jnp.int32)
        q = jnp.asarray(rng.standard_normal((b, kvh, g, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
        got = decode_attention_pallas(
            q, k, v, pos, block_s=128, interpret=True
        )
        want = decode_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=3e-5
        )


def test_decode_attention_matches_model_path():
    """Kernel == the model's decode_attend (the jnp path used in dry-runs)."""
    from repro.models.attention import decode_attend

    rng = np.random.default_rng(3)
    b, s, kvh, g, hd = 2, 256, 2, 4, 64
    h = kvh * g
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    pos = jnp.asarray(100, jnp.int32)
    got = decode_attention_pallas(
        q.reshape(b, kvh, g, hd), k, v, pos, block_s=128, interpret=True
    )
    want = decode_attend(q, k, v, pos)
    np.testing.assert_allclose(
        np.asarray(got.reshape(b, 1, h, hd)), np.asarray(want), atol=3e-5
    )


# ------------------------------------------------------ prefill_attention
def _prefill_case(seed, b, s, kvh, g, cq, hd=64):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, kvh, cq, g, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    # ragged per-slot offsets: every slot's chunk starts at its own depth
    pos = jnp.asarray(
        rng.integers(0, s - cq + 1, (b,)).astype(np.int32)
    )
    return q, k, v, pos


@pytest.mark.parametrize("cq", [1, 3, 8])
@pytest.mark.parametrize("kvh,g", [(1, 4), (2, 2), (4, 1)])
@pytest.mark.parametrize("s,block_s", [(256, 128), (300, 128)])
def test_prefill_attention_chunk_widths(cq, kvh, g, s, block_s):
    """Oracle parity across chunk widths C (C == 1 degenerates to the
    decode mask), GQA group shapes, and non-divisible cache lengths, with
    ragged per-slot position offsets."""
    q, k, v, pos = _prefill_case(cq * 10 + kvh, 2, s, kvh, g, cq)
    got = prefill_attention_pallas(
        q, k, v, pos, block_s=block_s, interpret=True
    )
    want = prefill_attention_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_prefill_attention_sliding_window():
    q, k, v, pos = _prefill_case(7, 2, 512, 2, 2, 5)
    got = prefill_attention_pallas(
        q, k, v, pos, block_s=128, window=64, interpret=True
    )
    want = prefill_attention_reference(q, k, v, pos, window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_prefill_attention_matches_model_path():
    """Chunk kernel == the model's decode_attend with C > 1 chunk queries
    (the serving prefill jnp path)."""
    from repro.models.attention import decode_attend

    rng = np.random.default_rng(11)
    b, s, kvh, g, cq, hd = 2, 256, 2, 4, 6, 64
    h = kvh * g
    q = jnp.asarray(rng.standard_normal((b, cq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    pos = jnp.asarray([40, 170], jnp.int32)
    qg = q.reshape(b, cq, kvh, g, hd).transpose(0, 2, 1, 3, 4)
    got = prefill_attention_pallas(qg, k, v, pos, block_s=128, interpret=True)
    got = got.transpose(0, 2, 1, 3, 4).reshape(b, cq, h, hd)
    want = decode_attend(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


if HAVE_HYPOTHESIS:

    @settings(deadline=None, max_examples=15)
    @given(
        s=st.integers(16, 512),
        cq=st.integers(1, 8),
        kvh=st.sampled_from([1, 2]),
        g=st.sampled_from([1, 3]),
        seed=st.integers(0, 10_000),
    )
    def test_prefill_attention_property(s, cq, kvh, g, seed):
        """Invariant: kernel == oracle for any cache length / chunk width /
        per-slot offsets, including chunks near the cache start (pos ~ 0)."""
        if cq > s:
            cq = s
        q, k, v, pos = _prefill_case(seed, 2, s, kvh, g, cq)
        got = prefill_attention_pallas(
            q, k, v, pos, block_s=128, interpret=True
        )
        want = prefill_attention_reference(q, k, v, pos)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=5e-5
        )


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("window", [None, 24])
def test_paged_prefill_attention_oracle(block_size, window):
    """Paged chunk kernel == gather-then-dense oracle at serving block
    sizes, GQA + sliding window, ragged per-slot offsets, shuffled block
    tables (physical pages deliberately out of logical order)."""
    rng = np.random.default_rng(13)
    b, kvh, g, cq, hd = 2, 2, 4, 5, 64
    max_blocks = 64 // block_size
    num_blocks = 2 * b * max_blocks + 1
    q = jnp.asarray(rng.standard_normal((b, kvh, cq, g, hd)), jnp.float32)
    k_pool = jnp.asarray(
        rng.standard_normal((num_blocks, block_size, kvh, hd)), jnp.float32
    )
    v_pool = jnp.asarray(
        rng.standard_normal((num_blocks, block_size, kvh, hd)), jnp.float32
    )
    bt = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks))[: b * max_blocks]
        .reshape(b, max_blocks).astype(np.int32)
    )
    pos = jnp.asarray([64 - cq, 17], jnp.int32)  # ragged slot depths
    got = paged_prefill_attention_pallas(
        q, k_pool, v_pool, bt, pos, window=window, interpret=True
    )
    want = paged_prefill_attention_reference(
        q, k_pool, v_pool, bt, pos, window=window
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_paged_prefill_attention_null_blocks_unreachable():
    """Table entries past a slot's allocation are 0 (the null block); the
    kv_idx <= pos + i mask must keep the null block's garbage out of every
    valid query's softmax."""
    rng = np.random.default_rng(17)
    b, kvh, g, cq, hd, bs, mb = 1, 2, 2, 4, 64, 8, 6
    num_blocks = 12
    q = jnp.asarray(rng.standard_normal((b, kvh, cq, g, hd)), jnp.float32)
    k_pool = jnp.asarray(
        rng.standard_normal((num_blocks, bs, kvh, hd)), jnp.float32
    )
    v_pool = jnp.asarray(
        rng.standard_normal((num_blocks, bs, kvh, hd)), jnp.float32
    )
    # slot holds 2 mapped blocks = 16 positions; the rest of the table is 0
    bt = jnp.asarray([[3, 7, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.asarray([16 - cq], jnp.int32)  # chunk fills the mapped span
    got = paged_prefill_attention_pallas(
        q, k_pool, v_pool, bt, pos, interpret=True
    )
    # oracle over ONLY the mapped prefix: poisoning the null block must not
    # change the output
    k_poison = k_pool.at[0].set(1e6)
    v_poison = v_pool.at[0].set(1e6)
    want = paged_prefill_attention_reference(q, k_pool, v_pool, bt, pos)
    got_poison = paged_prefill_attention_pallas(
        q, k_poison, v_poison, bt, pos, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    np.testing.assert_allclose(
        np.asarray(got_poison), np.asarray(got), atol=3e-5
    )


@pytest.mark.parametrize("layout", ["paged", "dense"])
@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("window", [None, 6])
def test_attention_walk_stops_at_kv_len(layout, block, window):
    """The walk bounded by ``kv_len`` (here 0, a part block, a whole block,
    the full table) leaves every valid row bitwise as the same kernel's
    walk over every entry gives it, and equal to the oracle; a slot with no
    valid query walks nothing and writes 0."""
    rng = np.random.default_rng(block + (window or 0))
    kvh, g, cq, hd, mb = 2, 2, 4, 64, 4
    s = mb * block
    # (pos, valid queries): none at a stale pos, a part block, a whole
    # block, the full table
    slots = ((5, 0), (2, 3), (block - cq, cq), (s - cq, cq))
    b = len(slots)
    pos = jnp.asarray([p for p, _ in slots], jnp.int32)
    kv_len = jnp.asarray([p + n if n else 0 for p, n in slots], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, kvh, cq, g, hd)), jnp.float32)
    k_pool, v_pool = (
        jnp.asarray(rng.standard_normal((b * mb + 1, block, kvh, hd)),
                    jnp.float32)
        for _ in range(2)
    )
    bt = jnp.asarray(rng.permutation(np.arange(1, b * mb + 1))
                     .reshape(b, mb).astype(np.int32))
    if layout == "paged":
        def attend(n):
            return paged_prefill_attention_pallas(
                q, k_pool, v_pool, bt, pos, n, window=window, interpret=True
            )
    else:
        k, v = (pool[bt].reshape(b, s, kvh, hd) for pool in (k_pool, v_pool))

        def attend(n):
            return prefill_attention_pallas(
                q, k, v, pos, n, block_s=block, window=window,
                interpret=True,
            )
    got = np.asarray(attend(kv_len))
    every = np.asarray(attend(jnp.full((b,), s, jnp.int32)))
    want = np.asarray(paged_prefill_attention_reference(
        q, k_pool, v_pool, bt, pos, window=window
    ))
    vb, vi = np.nonzero(np.arange(cq)[None, :] < np.asarray(slots)[:, 1:])
    np.testing.assert_array_equal(got[vb, :, vi], every[vb, :, vi])
    np.testing.assert_allclose(got[vb, :, vi], want[vb, :, vi], atol=3e-5)
    assert np.isfinite(got[0]).all()
    np.testing.assert_array_equal(got[0], 0.0)


# ---------------------------------------------- trace-count (recompilation)
def test_attention_ops_trace_once_across_pos_flavors():
    """Per-tick retrace regression: the public ops normalize ``pos`` (and
    block-table dtypes) BEFORE the jit boundary, so alternating Python
    ints, numpy scalars, () arrays and (B,) arrays — what a host serving
    loop actually passes tick to tick — hits ONE trace-cache entry per
    tensor shape on the jitted kernels."""
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.prefill_attention.ops import prefill_attention

    rng = np.random.default_rng(5)
    b, s, kvh, g, cq, hd = 2, 64, 2, 2, 4, 32
    h = kvh * g
    k = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, hd)), jnp.float32)
    q1 = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
    qc = jnp.asarray(rng.standard_normal((b, cq, h, hd)), jnp.float32)

    flavors = [
        7,  # python int
        np.int32(9),  # numpy scalar
        jnp.asarray(11, jnp.int32),  # () device array
        jnp.asarray([13, 5], jnp.int32),  # (B,) per-slot vector
        np.asarray([3, 21], np.int64),  # host vector, wrong dtype
    ]
    base_dec = decode_attention_pallas._cache_size()
    base_pre = prefill_attention_pallas._cache_size()
    for pos in flavors:
        decode_attention(q1, k, v, pos)
        prefill_attention(qc, k, v, pos)
    assert decode_attention_pallas._cache_size() == base_dec + 1
    assert prefill_attention_pallas._cache_size() == base_pre + 1
