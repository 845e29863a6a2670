"""ResNet roofline-lever variants: space_to_depth stem + per-block remat.

No cell of the benchmark runs these paths; this keeps a chip-independent
guard on the reshape/transpose math and on param-tree parity across the
remat flag.
"""
import pytest

import jax
import jax.numpy as jnp
import numpy as np

from kungfu_tpu.models.resnet import ResNet50
from kungfu_tpu.models.slp import softmax_cross_entropy

# compile-heavy: excluded from the fast dev loop (pytest -m 'not slow');
# CI runs the full suite unfiltered
pytestmark = pytest.mark.slow


def _variant(stem, remat):
    return ResNet50(
        num_classes=10, norm_dtype=jnp.bfloat16, stem=stem, remat=remat
    )


def _init(model, x):
    return model.init(jax.random.PRNGKey(0), x, train=False)


def test_remat_shares_param_tree_and_init():
    """remat is a memory strategy, not a different network: same tree
    paths, same same-seed params (stable block names defeat nn.remat's
    scope renaming)."""
    x = jnp.zeros((1, 64, 64, 3), jnp.bfloat16)
    v_plain = _init(_variant("conv7", False), x)
    v_remat = _init(_variant("conv7", True), x)
    paths_plain = {jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_flatten_with_path(v_plain["params"])[0]}
    paths_remat = {jax.tree_util.keystr(p)
                   for p, _ in jax.tree_util.tree_flatten_with_path(v_remat["params"])[0]}
    assert paths_plain == paths_remat
    chex_equal = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        v_plain["params"], v_remat["params"],
    )
    assert all(jax.tree.leaves(chex_equal))


def test_all_variants_train_and_agree_on_shapes():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 64, 64, 3), jnp.bfloat16)
    y = jnp.asarray([1, 2])
    out_shapes = set()
    for stem in ("conv7", "space_to_depth"):
        for remat in (False, True):
            m = _variant(stem, remat)
            v = _init(m, x)

            def loss(p, ms):
                logits, mut = m.apply(
                    {"params": p, **ms}, x, train=True,
                    mutable=["batch_stats"],
                )
                return softmax_cross_entropy(logits, y), mut

            (l, _), g = jax.jit(
                jax.value_and_grad(loss, has_aux=True)
            )(v["params"], {"batch_stats": v["batch_stats"]})
            assert np.isfinite(float(l)), (stem, remat)
            assert all(
                np.all(np.isfinite(np.asarray(leaf, np.float32)))
                for leaf in jax.tree.leaves(g)
            ), (stem, remat)
            logits = m.apply(v, x, train=False)
            out_shapes.add(tuple(logits.shape))
    # s2d stem halves H/W before stage 0 exactly like conv7's stride-2:
    # every variant must agree on the classifier shape
    assert out_shapes == {(2, 10)}


def test_s2d_packing_math():
    """The 2x2 pixel-block packing is position-preserving: each packed
    channel group reproduces the corresponding sub-grid."""
    b, h, w, c = 1, 4, 4, 3
    x = np.arange(b * h * w * c, dtype=np.float32).reshape(b, h, w, c)
    packed = x.reshape(b, h // 2, 2, w // 2, 2, c).transpose(
        0, 1, 3, 2, 4, 5
    ).reshape(b, h // 2, w // 2, 4 * c)
    # channel group (i2, j2) holds pixel (2i + i2, 2j + j2)
    for i2 in range(2):
        for j2 in range(2):
            grp = packed[..., (i2 * 2 + j2) * c:(i2 * 2 + j2 + 1) * c]
            np.testing.assert_array_equal(grp, x[:, i2::2, j2::2, :])
