import os

from harness import store


def test_prune_keeps_the_snapshots_used_last(tmp_path):
    for i, name in enumerate(["a", "b", "c", "d"]):
        os.makedirs(tmp_path / name)
        built = tmp_path / name / "BUILT"
        built.write_text("{}")
        os.utime(built, (1000 + i, 1000 + i))
    os.makedirs(tmp_path / "died")              # a build without BUILT
    os.utime(tmp_path / "a" / "BUILT", (2000, 2000))    # a run used it
    store.prune(str(tmp_path), keep=2)
    assert sorted(os.listdir(tmp_path)) == ["a", "d"]
    store.prune(str(tmp_path / "absent"))       # no store yet: nothing to do


def test_store_dir_is_fixed_by_scale_and_seed():
    assert store.store_dir("r", 1.0, 3000000017) == "r/sf1-seed3000000017"
    assert store.store_dir("r", 0.01, 7) == "r/sf0.01-seed7"
