import numpy as np
import pytest

from repro.core import tree as tree_mod


def test_build_tree_perm_is_permutation():
    r = np.random.default_rng(1)
    x = r.normal(size=(512, 3)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=64)
    assert t.levels == 3
    assert sorted(t.perm.tolist()) == list(range(512))
    inv = t.inverse_perm()
    assert np.all(t.perm[inv] == np.arange(512))


def test_tree_clusters_are_spatially_tight():
    # A tree on two widely separated blobs must not split any leaf across them.
    r = np.random.default_rng(2)
    xa = r.normal(size=(128, 2)) + np.array([100.0, 0.0])
    xb = r.normal(size=(128, 2)) - np.array([100.0, 0.0])
    x = np.concatenate([xa, xb]).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=32)
    xp = x[t.perm]
    for s in tree_mod.leaf_slices(t):
        leaf = xp[s]
        assert leaf[:, 0].max() - leaf[:, 0].min() < 50.0


def test_pad_dataset_inert():
    r = np.random.default_rng(3)
    x = r.normal(size=(100, 3)).astype(np.float32)
    y = np.sign(r.normal(size=100)).astype(np.float32)
    xp, yp, mask, levels = tree_mod.pad_dataset(x, y, leaf_size=32)
    assert xp.shape[0] == 32 * 2 ** levels >= 100
    assert mask.sum() == 100
    # pads are far from data AND from each other
    pads = xp[~mask]
    if len(pads) >= 2:
        d = np.linalg.norm(pads[0] - pads[1])
        assert d > 100.0
    d_data = np.linalg.norm(pads[0] - x, axis=1).min()
    assert d_data > 100.0


def test_padded_size():
    assert tree_mod.padded_size(100, 32) == (128, 2)
    assert tree_mod.padded_size(128, 32) == (128, 2)
    assert tree_mod.padded_size(129, 32) == (256, 3)


def test_build_tree_rejects_bad_n():
    x = np.zeros((100, 2), np.float32)
    with pytest.raises(ValueError):
        tree_mod.build_tree(x, leaf_size=32, levels=2)


def test_susy_rows_give_a_valid_permutation_and_only_continuous_splits():
    from bench.data import susy

    x, y = susy.generate(3000, (7, 1))
    xp, _, mask, levels = tree_mod.pad_dataset(x, y, leaf_size=256)
    t = tree_mod.build_tree(xp, leaf_size=256, levels=levels)
    assert t.n == 4096 and sorted(t.perm.tolist()) == list(range(4096))
    # no column holds only 0s and 1s, so every split is along a continuous one
    assert not np.all((xp == 0) | (xp == 1), axis=0).any()
    # the inert pads, far out along the first axis, fill the last leaves
    assert not mask[t.perm][-1024:].any()


def _widest_median_bisection(x, leaf_size, levels):
    groups = [np.arange(x.shape[0])]
    for _ in range(levels):
        nxt = []
        for g in groups:
            pts = x[g]
            dim = int(np.argmax(pts.max(axis=0) - pts.min(axis=0)))
            order = np.argsort(pts[:, dim], kind="stable")
            half = g.shape[0] // 2
            nxt.extend((g[order[:half]], g[order[half:]]))
        groups = nxt
    return np.concatenate(groups)


@pytest.mark.parametrize("rows,features", [(4096, 18), (2048, 3)])
def test_continuous_rows_give_widest_median_bisection(rows, features):
    r = np.random.default_rng(rows)
    x = r.normal(size=(rows, features)).astype(np.float32)
    t = tree_mod.build_tree(x, leaf_size=64)
    np.testing.assert_array_equal(
        t.perm, _widest_median_bisection(x, 64, t.levels))


def _combos(x):
    """One id per (wilderness, soil) pair of covtype-shaped rows."""
    return np.argmax(x[:, 10:14], axis=1) * 40 + np.argmax(x[:, 14:], axis=1)


def test_covtype_rows_keep_each_category_contiguous():
    from bench.data import covtype

    x, _ = covtype.generate(16384, (5, 1))
    t = tree_mod.build_tree(x, leaf_size=256)
    assert t.levels == 6 and sorted(t.perm.tolist()) == list(range(16384))
    ids = _combos(x)[t.perm]
    # every (wilderness, soil) pair is one run of the order, so each node
    # of the perfect tree (a run of it) cuts at most two pairs
    runs = 1 + np.count_nonzero(ids[1:] != ids[:-1])
    assert runs == np.unique(ids).shape[0]
    def per_leaf(perm):
        return np.mean([np.unique(leaf).shape[0]
                        for leaf in _combos(x)[perm].reshape(64, 256)])

    # widest-coordinate bisection splits on continuous columns only and
    # leaves ~80 pairs in a leaf here
    widest = per_leaf(_widest_median_bisection(x, 256, 6))
    assert widest > 60 and per_leaf(t.perm) < widest / 10
    # soil, the attribute of rarer categories, is mostly the outer one of
    # the order: its 40 values change far less often than the 4 areas do
    def changes(col):
        return np.count_nonzero(col[1:] != col[:-1])

    soil = changes(np.argmax(x[t.perm, 14:], axis=1))
    area = changes(np.argmax(x[t.perm, 10:14], axis=1))
    assert soil < 80 and 2 * soil < area, (soil, area)


def test_one_hot_leaves_are_tight_in_the_continuous_columns():
    from bench.data import covtype

    x, _ = covtype.generate(8192, (6, 1))
    t = tree_mod.build_tree(x, leaf_size=128)
    xp = x[t.perm]
    ids = _combos(x)[t.perm]
    # inside a pair's run the continuous columns are bisected: a leaf that
    # holds one pair spans less than the pair's whole range
    for leaf in range(t.n_leaves):
        s = slice(leaf * 128, (leaf + 1) * 128)
        pair = ids[s]
        if np.all(pair == pair[0]) and np.sum(ids == pair[0]) >= 512:
            whole = xp[ids == pair[0], :10]
            part = xp[s, :10]
            assert np.any((part.max(0) - part.min(0))
                          < 0.75 * (whole.max(0) - whole.min(0)))
