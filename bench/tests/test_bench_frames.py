"""The frame generator: each content class routes as named at the paper's
thresholds (t1 = 8, t2 = 40) through the program's own edge score, and the
seed alone fixes the frames."""
import jax.numpy as jnp
import numpy as np
import pytest

import tinyroot  # noqa: F401  (puts bench/ and src/ on the path)
import frames
from repro.core.edge_score import edge_score
from repro.core.patching import get_geometry
from repro.core.subnet_policy import decide

HW, CELL = (240, 360), 30


@pytest.mark.parametrize("seed", [0, 5, 2**33 + 1])
def test_each_class_routes_as_named(seed):
    n = (HW[0] // CELL) * (HW[1] // CELL)
    counts = frames.class_counts({"smooth": 1, "texture": 1, "edges": 1}, n)
    key = frames.root_key(seed)
    img = frames.make_frame(key, HW, CELL, counts)
    cls = np.asarray(frames.cell_classes(key, HW, CELL, counts)).reshape(-1)
    geom = get_geometry(HW[0], HW[1], 32, 2, 4)
    scores = np.asarray(edge_score(geom.extract(img)))
    ids = np.asarray(decide(jnp.asarray(scores), 8.0, 40.0))
    assert ids.shape == cls.shape
    np.testing.assert_array_equal(ids, cls)       # 0 bilinear, 1 C27, 2 C54
    # with room to spare at both thresholds
    assert scores[cls == 0].max() < 2.0
    assert 12.0 < scores[cls == 1].min() and scores[cls == 1].max() < 30.0
    assert scores[cls == 2].min() > 55.0


def test_class_counts_follow_the_shares_exactly():
    assert frames.class_counts({"smooth": 0.3, "texture": 0.3, "edges": 0.4},
                               9216) == [2765, 2765, 3686]
    assert frames.class_counts({"edges": 1.0}, 2304) == [0, 0, 2304]
    assert sum(frames.class_counts({"smooth": 0.93, "texture": 0.07}, 2304)) == 2304


def test_pool_is_fixed_by_the_seed():
    shares = {"smooth": 1, "texture": 1, "edges": 1}
    a = frames.make_pool(2**40 + 3, (60, 90), CELL, shares, 2)
    b = frames.make_pool(2**40 + 3, (60, 90), CELL, shares, 2)
    c = frames.make_pool(7, (60, 90), CELL, shares, 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], a[1])
    assert not np.array_equal(a[0], c[0])
    assert all(float(f.min()) >= 0.0 and float(f.max()) <= 1.0 for f in a)
