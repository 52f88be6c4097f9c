"""Pure-jnp oracle for the forest-scoring kernel (no Pallas).

Semantically identical to :func:`repro.forest.scoring.score_bitvector`, kept
self-contained here per the kernels/ convention so the kernel test sweep has
a dependency-free reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ALL_ONES = jnp.uint32(0xFFFFFFFF)


def _ctz_words(words: jax.Array) -> jax.Array:
    """Trailing zeros of ``words[..., w]`` (bits ``[32w, 32w + 32)``)."""
    leaf = None
    for w in reversed(range(words.shape[-1])):
        m = words[..., w]
        ctz = jax.lax.population_count(~m & (m - jnp.uint32(1))).astype(
            jnp.int32
        ) + jnp.int32(32 * w)
        leaf = ctz if leaf is None else jnp.where(m != 0, ctz, leaf)
    return leaf


def leaf_values_ref(leaf: jax.Array, leaf_value: jax.Array) -> jax.Array:
    """Gather oracle for the kernel's leaf-gather paths: a plain
    ``take_along_axis`` of ``leaf_value[t, leaf[b, t]]`` — the exact values
    every in-kernel path (one-hot, select tree, MXU contraction) must
    reproduce bit-for-bit. ``leaf: [B, T] i32``, ``leaf_value: [T, L]``."""
    return jnp.take_along_axis(leaf_value[None], leaf[:, :, None], axis=2)[..., 0]


def forest_score_ref(
    x: jax.Array,
    feature: jax.Array,
    threshold: jax.Array,
    mask: jax.Array,
    leaf_value: jax.Array,
) -> jax.Array:
    """x: [B, F]; tree arrays [T, N] / [T, N, W] / [T, L] → scores [B] f32."""
    xf = x[:, feature]                                  # [B, T, N]
    pred_true = xf <= threshold[None]
    m = jnp.where(pred_true[..., None], ALL_ONES, mask[None])
    words = jax.lax.reduce(m, ALL_ONES, jax.lax.bitwise_and, dimensions=(2,))
    leaf = _ctz_words(words)                            # [B, T]
    per_tree = leaf_values_ref(leaf, leaf_value)
    return per_tree.sum(axis=1).astype(jnp.float32)
