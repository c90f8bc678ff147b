"""Backend detection + attention-backend resolution for the Pallas kernels.

Two concerns live here, both serving-platform policy rather than kernel
math:

* ``resolve_interpret`` — whether a Pallas kernel runs COMPILED (on TPU)
  or in INTERPRET mode (everywhere else, so CPU test runs execute the real
  kernel bodies). Callers can always override with an explicit
  ``interpret=`` argument — CPU tests pass ``interpret=True`` so they stay
  deterministic regardless of the machine they run on. Nothing here proves
  a kernel was compiled: a chip run checks its program's compiled text for
  ``tpu_custom_call`` (``chip_smoke.py``, ``tests/test_tpu_compile.py``).

* ``resolve_attn_backend`` — the per-layer fallback matrix for the serving
  attention backend flag (``ArchConfig.attn_backend``). The Pallas flash
  kernels cover GQA decode + chunked prefill in both the dense and the
  block-table paged cache layouts (causal and sliding-window); everything
  else silently uses the jnp path, never errors:

    layer kind          | "jnp"  | "pallas"
    --------------------|--------|---------------------------------
    GQA (dense cache)   | jnp    | flash decode / flash prefill
    GQA (paged cache)   | jnp    | paged flash decode / prefill
    GQA sliding window  | jnp    | flash kernels (windowed mask)
    MLA (DeepSeek)      | jnp    | jnp fallback (absorbed-matrix
                        |        | decode runs in the compressed
                        |        | latent space; no K/V heads exist
                        |        | for a flash kernel to stream)
    mamba2 / xLSTM      | jnp    | jnp (recurrent state update —
                        |        | there is no attention to flash)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ATTN_BACKENDS = ("jnp", "pallas")


def pos_vector(pos, b: int) -> jax.Array:
    """Normalize ()/(B,)/python-int positions to a (B,) int32 array.

    Called by the kernel ops BEFORE their jit boundary: the serving loop
    passes whatever the host holds tick to tick (Python ints during warmup,
    numpy scalars, () or (B,) device arrays), and every flavor would
    otherwise be a distinct trace-cache entry on the jitted kernels. One
    (B,) int32 aval per tensor shape means ONE trace — asserted by the
    single-trace regression in tests/test_kernels.py."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit ``interpret`` wins; otherwise compile on TPU and interpret
    everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def resolve_attn_backend(backend: str, *, mla: bool = False) -> str:
    """Effective attention backend for one serving attention layer.

    Implements the fallback matrix in the module docstring: "pallas" is
    honored for GQA layers (dense or paged, windowed or not) and silently
    degrades to "jnp" for MLA — the absorbed-matrix MLA decode contracts
    queries against the compressed c_kv cache, so there are no materialized
    K/V heads for the flash kernels to stream. Recurrent (mamba2 / xLSTM)
    blocks never reach this function: they have no attention.

    Unknown backend names raise — a typo must not silently serve the slow
    path.
    """
    if backend not in ATTN_BACKENDS:
        raise ValueError(
            f"attn_backend must be one of {ATTN_BACKENDS}, got {backend!r}"
        )
    if backend == "pallas" and mla:
        return "jnp"
    return backend
