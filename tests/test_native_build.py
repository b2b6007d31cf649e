"""native/build.py: libraries are keyed by what they are built from."""

import os

from photon_ml_tpu.native import build


def test_library_name_is_content_keyed_not_mtime_keyed(tmp_path, monkeypatch):
    src = tmp_path / "thing.cpp"
    src.write_text("int f() { return 1; }\n")
    first = build._lib_path(str(src))
    assert os.path.dirname(first) == build.BUILD_DIR
    os.utime(src, (1, 1))  # a copy or a checkout changes mtimes
    assert build._lib_path(str(src)) == first
    src.write_text("int f() { return 2; }\n")  # an edit changes the key
    edited = build._lib_path(str(src))
    assert edited != first
    monkeypatch.setattr(build, "_CXX_FLAGS", build._CXX_FLAGS + ["-O3"])
    assert build._lib_path(str(src)) != edited  # so does a flag


def test_builds_land_in_the_ignored_directory_only():
    from photon_ml_tpu.native.build import libsvm_native_available

    if not libsvm_native_available():
        return  # no compiler here: nothing was built
    native_dir = os.path.dirname(build.__file__)
    assert not [f for f in os.listdir(native_dir) if f.endswith(".so")]
    built = [f for f in os.listdir(build.BUILD_DIR)
             if f.startswith("libsvm_loader-") and f.endswith(".so")]
    assert len(built) == 1  # stale builds of the same source are removed
