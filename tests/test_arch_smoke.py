"""Per-architecture smoke tests (assignment requirement): a REDUCED variant of
each family (2 layers, d_model <= 512, <= 4 experts) runs one forward + one
train step + one decode step on CPU; output shapes checked, no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, list_archs
from repro.models import TransformerLM

ARCHS = [a for a in list_archs() if a != "multitask_linreg"]
B, S = 2, 32


def make_batch(cfg, rng, seq=S, batch=B):
    b = {"task_ids": np.arange(batch, dtype=np.int32) % cfg.num_tasks}
    if cfg.input_mode == "audio":
        b["tokens"] = rng.integers(0, cfg.vocab_size, (batch, seq, cfg.num_codebooks)).astype(np.int32)
        b["labels"] = rng.integers(0, cfg.vocab_size, (batch, seq, cfg.num_codebooks)).astype(np.int32)
    else:
        b["tokens"] = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        b["labels"] = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        if cfg.input_mode == "vlm":
            b["vision_embeds"] = rng.standard_normal((batch, seq, cfg.d_model)).astype(np.float32)
            mask = np.zeros((batch, seq), bool)
            mask[:, : seq // 4] = True
            b["vision_mask"] = mask
    return {k: jnp.asarray(v) for k, v in b.items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = make_batch(cfg, rng)

    logits, aux = jax.jit(model.forward)(params, batch)
    if cfg.num_codebooks > 1:
        assert logits.shape == (B, S, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))

    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss_fn, has_aux=True)
    )(params, batch)
    assert bool(jnp.isfinite(loss))
    flat = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in flat)
    # one SGD step changes the loss
    params2 = jax.tree.map(lambda p, g: p - 1e-2 * g, params, grads)
    loss2, _ = jax.jit(model.loss_fn)(params2, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch):
    cfg = get(arch, smoke=True)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    max_seq = 16
    caches = model.init_cache(B, max_seq)
    batch = make_batch(cfg, rng, seq=1)
    logits, caches = jax.jit(model.decode_step, static_argnames=())(
        params, batch, caches, 0
    )
    want = (
        (B, 1, cfg.num_codebooks, cfg.vocab_size)
        if cfg.num_codebooks > 1
        else (B, 1, cfg.vocab_size)
    )
    assert logits.shape == want
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize(
    "arch",
    ["qwen2_5_14b", "zamba2_7b", "xlstm_350m", "deepseek_v2_236b",
     "granite_4_h_micro"],
)
def test_prefill_decode_consistency(arch):
    """prefill(t_0..t_{n-1}) then decode(t_n) must match the full forward."""
    import dataclasses

    cfg = get(arch, smoke=True)
    if cfg.uses_moe:
        # dropless capacity so routing decisions are identical between the
        # batched full pass and the single-token decode pass
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    seq = 8
    full = make_batch(cfg, rng, seq=seq)
    logits_full, _ = jax.jit(model.forward)(params, full)

    prefix = {k: (v[:, : seq - 1] if v.ndim > 1 else v) for k, v in full.items()}
    _, caches = jax.jit(lambda p, b: model.prefill(p, b, seq))(params, prefix)
    last = {
        "tokens": full["tokens"][:, seq - 1 : seq],
        "task_ids": full["task_ids"],
    }
    if cfg.input_mode == "vlm":
        last["vision_embeds"] = full["vision_embeds"][:, seq - 1 : seq]
        last["vision_mask"] = full["vision_mask"][:, seq - 1 : seq]
    logits_dec, _ = jax.jit(model.decode_step)(params, last, caches, seq - 1)

    a = np.asarray(logits_full[:, -1]).reshape(B, -1)
    b = np.asarray(logits_dec[:, 0]).reshape(B, -1)
    np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)
