"""The rule the benchmark is built on: the configuration fixes the work, the
seed fixes the values."""

import numpy as np
import pytest

from benchmark import datagen
from bm_helpers import TINY, tiny_cell


@pytest.mark.parametrize("count,total,a,lo,hi", [
    (300, 40000, 1.0, 20, 9254),
    (250, 40000, 1.8, 1, 16828),
    (34623, 4999168, 1.0, 20, 9254),
    (26744, 4999168, 1.8, 1, 16828),
    (7, 100, 0.5, 1, 40),
])
def test_size_profile_sums_to_the_row_count(count, total, a, lo, hi):
    sizes = datagen.size_profile(count, total, a, lo, hi)
    assert sizes.sum() == total and len(sizes) == count
    assert sizes.min() >= lo and sizes.max() <= hi
    assert (np.diff(sizes) <= 1).all()  # by rank, but for the remainder's +1


def test_size_profile_rejects_what_cannot_fit():
    with pytest.raises(ValueError):
        datagen.size_profile(10, 5, 1.0, 1, 100)
    with pytest.raises(ValueError):
        datagen.size_profile(10, 5000, 1.0, 1, 100)


@pytest.fixture(scope="module")
def two_seeds():
    cfg = tiny_cell("glmix-ml20m.sweeps")["config"]
    return cfg, datagen.make_glmix(cfg, 11), datagen.make_glmix(cfg, 4000000123)


def test_two_seeds_same_entity_size_histogram_other_ids(two_seeds):
    cfg, a, b = two_seeds
    for key, count in (("user", cfg["users"]["count"]), ("item", cfg["items"]["count"])):
        ha = np.sort(np.bincount(a["train"][key], minlength=count))
        hb = np.sort(np.bincount(b["train"][key], minlength=count))
        assert (ha == hb).all()
        assert (ha[::-1] == np.sort(a[key + "_sizes"])[::-1]).all()
        assert not (a["train"][key] == b["train"][key]).all()  # other ids
    # the training rows are the data set's own, whatever the seed (their
    # order decides float32 rounding, and rounding the solvers' work) ...
    for key in ("x_global", "x_user", "x_item", "y"):
        assert np.array_equal(a["train"][key], b["train"][key])
    # ... and a renaming of the entities apart, the two seeds pose one fit
    for key in ("user", "item"):
        rename = np.full(cfg[key + "s"]["count"], -1)
        rename[a["train"][key]] = b["train"][key]
        assert len(np.unique(rename)) == len(rename)
        assert (rename[a["train"][key]] == b["train"][key]).all()
    # the validation rows are the same rows in another order
    for key in ("x_global", "y"):
        va, vb = a["validation"][key], b["validation"][key]
        assert va.shape == vb.shape and not np.array_equal(va, vb)
        assert np.array_equal(np.sort(va.astype(np.float64).sum(-1) if va.ndim > 1 else va),
                              np.sort(vb.astype(np.float64).sum(-1) if vb.ndim > 1 else vb))
    # non-zeros per row are the configuration's, for every row of every seed
    w = cfg["widths"]
    for d in (a, b):
        assert ((d["train"]["x_global"][:, :-1] != 0).sum(1) == w["global_nnz"]).all()
        assert ((d["train"]["x_user"][:, :-1] != 0).sum(1) == w["entity_nnz"]).all()
        assert (d["train"]["x_global"][:, -1] == 1).all()


def test_one_seed_twice_is_bitwise_equal(two_seeds):
    cfg, a, _ = two_seeds
    again = datagen.make_glmix(cfg, 11)
    for split in ("train", "validation"):
        for key, value in a[split].items():
            assert value.tobytes() == again[split][key].tobytes(), (split, key)


def test_two_seeds_pack_into_the_same_block_shapes(two_seeds):
    """Every [e, cap, d] block, every padded size: the compiled programs of
    two seeds are the same programs."""
    import jax

    from benchmark.drivers import game_sweeps
    from benchmark.spans import Spans

    cfg, a, b = two_seeds
    shapes = []
    for data in (a, b):
        cell = game_sweeps.Cell(cfg, {}, 0, jax.devices()[:1], Spans(), data=data)
        shapes.append({
            t: [(tuple(bk.features.shape), tuple(bk.sample_rows.shape),
                 int((np.asarray(bk.sample_rows) >= 0).sum()))
                for bk in ds.buckets]
            for t, ds in cell.re_datasets.items()})
        assert {k: v.shape for k, v in cell.dataset.feature_shards.items()} == {
            "global": (cfg["rows"], 256), "per_user": (cfg["rows"], 16),
            "per_item": (cfg["rows"], 16)}
    assert shapes[0] == shapes[1]
    caps = [s[0][1] for s in shapes[0]["user"]]
    assert caps == sorted(caps) and set(caps) <= set(cfg["bucket_ladder"])


@pytest.mark.parametrize("workload", sorted(w for w in TINY if "users" in TINY[w]))
def test_full_size_structure_is_a_function_of_the_configuration(workload):
    """At the committed sizes: the entity sizes need no seed at all."""
    from benchmark.manifest import find_cell, load_manifest

    cfg = find_cell(load_manifest(), workload)["config"]
    for key in ("users", "items"):
        sizes = datagen.entity_sizes(cfg[key], cfg["rows"])
        assert sizes.sum() == cfg["rows"]
        assert sizes[0] == cfg[key]["max"] and sizes[-1] >= cfg[key]["min"]
    assert cfg["rows"] % (1024 * cfg["mesh"]["data"]) == 0


def test_two_seeds_pose_the_same_fit_entity_for_entity(two_seeds):
    """What keeps the work equal on the chip: renamed entities are solved
    from the same rows in the same order, so the coefficients of an entity
    do not depend on the seed."""
    import jax

    from benchmark.drivers import game_sweeps
    from benchmark.spans import Spans

    cfg, a, b = two_seeds
    fits = []
    for data in (a, b):
        cell = game_sweeps.Cell(cfg, {}, 0, jax.devices()[:1], Spans(), data=data)
        fits.append(cell.episode())
    assert fits[0]["losses"] == pytest.approx(fits[1]["losses"], rel=1e-6)
    np.testing.assert_allclose(fits[0]["fe"], fits[1]["fe"], rtol=1e-4, atol=1e-6)
    for key in ("user", "item"):
        rename = np.zeros(cfg[key + "s"]["count"], np.int64)
        rename[a["train"][key]] = b["train"][key]
        np.testing.assert_allclose(fits[0][key], fits[1][key][rename],
                                   rtol=1e-4, atol=1e-6)
