"""Synthetic data: ``synth_imagenet_features`` keeps its historical values by
default and has a float32 draw that never builds a float64 table."""
import numpy as np

from repro.data.synthetic import synth_imagenet_features


def test_imagenet_features_default_values_unchanged():
    """The default draw is bit-identical to the original float64 recipe."""
    n, d, seed = 64, 40, 3
    rng = np.random.default_rng(seed)
    X_old = np.maximum(rng.normal(size=(n, d)), 0).astype(np.float32)
    w = rng.normal(size=d) / np.sqrt(d)
    y_old = ((X_old @ w) > np.median(X_old @ w)).astype(np.float32)

    X, y = synth_imagenet_features(n, d, seed=seed)
    assert X.dtype == np.float32 and y.dtype == np.float32
    np.testing.assert_array_equal(X, X_old)
    np.testing.assert_array_equal(y, y_old)


def test_imagenet_features_float32_values_pinned():
    X, y = synth_imagenet_features(64, 40, seed=3, dtype=np.float32)
    assert X.dtype == np.float32 and y.dtype == np.float32
    np.testing.assert_array_equal(
        X[0, :4], np.asarray([2.41715, 0.14276257, 0.0, 0.0], np.float32))
    assert "".join(str(int(v)) for v in y[:16]) == "0001000011100001"
    # ReLU'd features and a median split, as in the default draw
    assert X.min() == 0.0 and y.sum() == 32.0
