"""Runtime-checked lane for the kernel entry points' jaxtyping
annotations (tier-1).

``shape_checked`` enforces the declared shapes/dtypes at call time with
dim variables bound across arguments — the node axis ``n`` on
``feature`` must be the SAME ``n`` as on ``threshold``/``mask``, and
the tree axis ``t`` must agree everywhere.  Production call sites stay
unwrapped; this lane proves the annotations are truthful.
"""

from __future__ import annotations

import jax.numpy as jnp
import pytest

jaxtyping = pytest.importorskip("jaxtyping")

from repro.kernels.forest_score import (  # noqa: E402
    forest_score_pallas,
    forest_score_segments_pallas,
)
from repro.typecheck import shape_checked  # noqa: E402

B, F, T, N, L = 8, 4, 16, 8, 4


def _operands():
    return dict(
        x=jnp.zeros((B, F), jnp.float32),
        feature=jnp.zeros((T, N), jnp.int32),
        threshold=jnp.zeros((T, N), jnp.float32),
        mask=jnp.full((T, N, 1), 0xFFFFFFFF, jnp.uint32),
        leaf_value=jnp.zeros((T, L), jnp.float32),
    )


def test_plain_entry_accepts_declared_shapes():
    checked = shape_checked(forest_score_pallas)
    out = checked(**_operands(), block_b=B, block_t=T)
    assert out.shape == (B,)
    assert out.dtype == jnp.float32


def test_segments_entry_accepts_and_returns_b_s():
    checked = shape_checked(forest_score_segments_pallas)
    out = checked(
        **_operands(),
        seg_block_starts=(0,), n_tree_blocks=1, block_b=B, block_t=T,
    )
    assert out.shape == (B, 1)


def test_wrong_dtype_rejected():
    checked = shape_checked(forest_score_pallas)
    ops = _operands()
    ops["feature"] = ops["feature"].astype(jnp.float32)  # i32 contract
    with pytest.raises(TypeError, match="feature"):
        checked(**ops, block_b=B, block_t=T)


def test_cross_argument_dim_binding_rejected():
    # threshold's node axis disagrees with feature's — same letter `n`
    # in the annotation, so the binding must fail even though each
    # operand is a valid [t, n] float32/int32 on its own.
    checked = shape_checked(forest_score_pallas)
    ops = _operands()
    ops["threshold"] = jnp.zeros((T, 2 * N), jnp.float32)
    with pytest.raises(TypeError, match="threshold"):
        checked(**ops, block_b=B, block_t=T)


def test_wrong_rank_rejected():
    checked = shape_checked(forest_score_pallas)
    ops = _operands()
    ops["x"] = jnp.zeros((B,), jnp.float32)
    with pytest.raises(TypeError, match="`x`"):
        checked(**ops, block_b=B, block_t=T)


def test_unwrapped_entry_points_unchanged():
    # the hot path never pays for checking: the public names are the
    # raw jitted callables, not shape_checked wrappers
    assert not hasattr(forest_score_pallas, "__shape_checked__")
    out = forest_score_pallas(**_operands(), block_b=B, block_t=T)
    assert out.shape == (B,)
