"""Util subsystem tests (reference core/util/*Test.java tier)."""

import numpy as np
import pytest

from deeplearning4j_tpu.utils import (
    DiskBasedQueue,
    ImageLoader,
    MovingWindowMatrix,
    Viterbi,
    math_utils,
    read_object,
    save_object,
    unzip_file_to,
)


class TestViterbi:
    def test_smooths_isolated_flips(self):
        # a long run of state 0 with one observation error -> decoded
        # sequence removes the flip (metaStability favors staying; with
        # p_correct=0.9 one mismatch is cheaper than two transitions)
        observed = np.array([0, 0, 0, 1, 0, 0, 0])
        v = Viterbi(np.array([0, 1]), p_correct=0.9)
        logp, path = v.decode(observed, binary_label_matrix=False)
        np.testing.assert_array_equal(path, np.zeros(7))
        assert logp < 0

    def test_respects_persistent_switch(self):
        observed = np.array([0, 0, 0, 1, 1, 1, 1])
        v = Viterbi(np.array([0, 1]))
        _, path = v.decode(observed, binary_label_matrix=False)
        np.testing.assert_array_equal(path, observed)

    def test_binary_label_matrix_input(self):
        labels = np.eye(3)[[2, 2, 2, 2]]
        v = Viterbi(np.array([0, 1, 2]))
        _, path = v.decode(labels)
        np.testing.assert_array_equal(path, [2, 2, 2, 2])

    def test_empty_rejected(self):
        v = Viterbi(np.array([0, 1]))
        with pytest.raises(ValueError):
            v.decode(np.array([]), binary_label_matrix=False)


class TestMathUtils:
    def test_normalize_discretize_clamp(self):
        assert math_utils.normalize(5, 0, 10) == 0.5
        assert math_utils.clamp(12, 0, 10) == 10
        assert math_utils.discretize(0.99, 0, 1, 10) == 9
        assert math_utils.discretize(0.0, 0, 1, 10) == 0

    def test_next_pow_2(self):
        assert math_utils.next_pow_2(1) == 1
        assert math_utils.next_pow_2(5) == 8
        assert math_utils.next_pow_2(64) == 64

    def test_entropy_information(self):
        assert math_utils.entropy([1.0]) == pytest.approx(0.0)
        assert math_utils.information([0.5, 0.5]) == pytest.approx(-1.0)

    def test_tfidf(self):
        t = math_utils.tf(9)  # log10(10) = 1
        i = math_utils.idf(100, 9)  # log10(10) = 1
        assert math_utils.tfidf(t, i) == pytest.approx(1.0)

    def test_ols_weights(self):
        x = [1.0, 2.0, 3.0, 4.0]
        y = [3.0, 5.0, 7.0, 9.0]  # y = 2x + 1
        assert math_utils.w_1(x, y, 4) == pytest.approx(2.0)
        assert math_utils.w_0(x, y, 4) == pytest.approx(1.0)
        assert math_utils.squared_loss(x, y, 1.0, 2.0) == pytest.approx(0.0)

    def test_rmse_and_determination(self):
        assert math_utils.root_means_squared_error(
            [1, 2, 3], [1, 2, 3]) == 0.0
        assert math_utils.determination_coefficient(
            [1, 2, 3], [2, 4, 6], 3) == pytest.approx(1.0)

    def test_logs2probs(self):
        p = math_utils.logs2probs([0.0, 0.0])
        np.testing.assert_allclose(p, [0.5, 0.5])

    def test_string_similarity(self):
        assert math_utils.string_similarity("night", "night") == 1.0
        assert math_utils.string_similarity("night", "nacht") == \
            pytest.approx(0.25)
        assert math_utils.string_similarity("ab", "cd") == 0.0

    def test_combinatorics(self):
        assert math_utils.combination(5, 2) == 10
        assert math_utils.permutation(5, 2) == 20
        assert math_utils.prob_to_log_odds(0.5) == 0.0


class TestDiskBasedQueue:
    def test_fifo_spill_round_trip(self, tmp_path):
        with DiskBasedQueue(str(tmp_path / "q")) as q:
            q.add({"step": 1, "params": np.arange(4.0)})
            q.add({"step": 2, "params": np.ones((2, 2))})
            assert q.size() == 2
            # payloads live on disk, not RAM
            import os
            assert len(os.listdir(q.dir)) == 2
            first = q.poll()
            assert first["step"] == 1
            np.testing.assert_array_equal(first["params"], np.arange(4.0))
            assert q.poll()["step"] == 2
            assert q.poll() is None
            assert q.is_empty()

    def test_peek_does_not_remove(self, tmp_path):
        with DiskBasedQueue(str(tmp_path / "q")) as q:
            q.add("hello")
            assert q.peek() == "hello"
            assert q.size() == 1

    def test_drain_iterator(self, tmp_path):
        with DiskBasedQueue(str(tmp_path / "q")) as q:
            q.add_all([1, 2, 3])
            assert list(q) == [1, 2, 3]
            assert q.is_empty()

    def test_remove_on_empty_raises(self, tmp_path):
        with DiskBasedQueue(str(tmp_path / "q")) as q:
            with pytest.raises(IndexError):
                q.remove()


class TestSerialization:
    def test_round_trip(self, tmp_path):
        obj = {"a": np.eye(3), "b": [1, 2, {"c": "x"}], "d": None}
        path = save_object(obj, str(tmp_path / "obj.bin"))
        loaded = read_object(path)
        np.testing.assert_array_equal(loaded["a"], np.eye(3))
        assert loaded["b"] == [1, 2, {"c": "x"}]
        assert loaded["d"] is None


class TestMovingWindowMatrix:
    def test_all_windows(self):
        m = np.arange(16).reshape(4, 4)
        wins = MovingWindowMatrix(m, 2, 2).windows()
        assert len(wins) == 9  # 3x3 offsets
        np.testing.assert_array_equal(wins[0], [[0, 1], [4, 5]])
        np.testing.assert_array_equal(wins[-1], [[10, 11], [14, 15]])

    def test_flattened_and_rotate(self):
        m = np.arange(4).reshape(2, 2)
        plain = MovingWindowMatrix(m, 2, 2).windows(flattened=True)
        assert len(plain) == 1 and plain[0].shape == (4,)
        rot = MovingWindowMatrix(m, 2, 2, add_rotate=True).windows()
        assert len(rot) == 4  # original + 3 rotations
        np.testing.assert_array_equal(rot[1], np.rot90(m))

    def test_window_too_big_rejected(self):
        with pytest.raises(ValueError):
            MovingWindowMatrix(np.eye(2), 3, 3)


class TestImageLoaderAndArchive:
    def test_image_round_trip(self, tmp_path):
        from PIL import Image

        arr = (np.arange(100).reshape(10, 10) * 2).astype(np.uint8)
        p = str(tmp_path / "img.png")
        Image.fromarray(arr, mode="L").save(p)
        loader = ImageLoader(height=5, width=5)
        mat = loader.as_matrix(p)
        assert mat.shape == (5, 5) and mat.dtype == np.float32
        assert loader.as_row_vector(p).shape == (25,)
        assert loader.shape == (5, 5)

    def test_unzip(self, tmp_path):
        import zipfile

        z = str(tmp_path / "a.zip")
        with zipfile.ZipFile(z, "w") as f:
            f.writestr("sub/data.txt", "hello")
        dest = str(tmp_path / "out")
        unzip_file_to(z, dest)
        assert (tmp_path / "out" / "sub" / "data.txt").read_text() == "hello"

    def test_zip_traversal_rejected(self, tmp_path):
        import zipfile

        z = str(tmp_path / "evil.zip")
        with zipfile.ZipFile(z, "w") as f:
            f.writestr("../escape.txt", "bad")
        with pytest.raises(ValueError):
            unzip_file_to(z, str(tmp_path / "out2"))

    def test_tar_symlink_escape_rejected(self, tmp_path):
        import io
        import tarfile

        # symlink member pointing outside dest + a file written through it:
        # member names alone pass the prefix check, filter="data" must
        # reject the link
        t = str(tmp_path / "evil.tar")
        outside = tmp_path / "outside"
        outside.mkdir()
        with tarfile.open(t, "w") as f:
            link = tarfile.TarInfo("link")
            link.type = tarfile.SYMTYPE
            link.linkname = str(outside)
            f.addfile(link)
            payload = tarfile.TarInfo("link/evil.txt")
            data = b"bad"
            payload.size = len(data)
            f.addfile(payload, io.BytesIO(data))
        with pytest.raises(tarfile.FilterError):
            unzip_file_to(t, str(tmp_path / "out3"))
        assert not (outside / "evil.txt").exists()


class TestSanitize:
    """reference numerical guards: assertValidNum / NaN scrub / shape
    asserts (SURVEY §5 sanitizers)."""

    def test_assert_valid_num(self):
        from deeplearning4j_tpu.utils.sanitize import assert_valid_num

        assert_valid_num(np.ones(3), "ok")
        with pytest.raises(ValueError, match="2 NaN, 1 Inf"):
            assert_valid_num(np.array([1.0, np.nan, np.nan, np.inf]), "bad")

    def test_scrub_nan_is_jittable(self):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.utils.sanitize import scrub_nan

        x = jnp.array([1.0, jnp.nan, 3.0])
        out = jax.jit(scrub_nan)(x)
        np.testing.assert_allclose(np.asarray(out), [1.0, 1e-6, 3.0])

    def test_debug_nans_context(self):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.utils.sanitize import debug_nans

        prev = jax.config.jax_debug_nans
        with debug_nans():
            assert jax.config.jax_debug_nans
            with pytest.raises(FloatingPointError):
                jax.jit(lambda x: jnp.log(x))(jnp.array(-1.0)).block_until_ready()
        assert jax.config.jax_debug_nans == prev

    def test_validate_batch_messages(self):
        from deeplearning4j_tpu.utils.sanitize import validate_batch

        x = np.ones((4, 5), np.float32)
        y = np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]
        validate_batch(x, y, n_in=5, n_out=3)
        with pytest.raises(ValueError, match="n_in is 4"):
            validate_batch(x, y, n_in=4)
        with pytest.raises(ValueError, match="n_out is 2"):
            validate_batch(x, y, n_in=5, n_out=2)
        with pytest.raises(ValueError, match="label rows"):
            validate_batch(x, y[:3], n_in=5, n_out=3)
        with pytest.raises(ValueError, match="at least 2-D"):
            validate_batch(np.ones(4))

    def test_multilayer_rejects_bad_width_with_clear_error(self):
        from deeplearning4j_tpu.config import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder()
                .lr(0.1).n_in(4).activation_function("tanh")
                .optimization_algo("iteration_gradient_descent")
                .num_iterations(1)
                .list(2).hidden_layer_sizes([8])
                .override(1, layer="output", loss_function="mcxent",
                          activation_function="softmax", n_out=3)
                .pretrain(False).build())
        net = MultiLayerNetwork(conf)
        bad = np.ones((2, 5), np.float32)
        with pytest.raises(ValueError, match="n_in is 4"):
            net.output(bad)
        with pytest.raises(ValueError, match="n_in is 4"):
            net.fit(bad, np.eye(3, dtype=np.float32)[[0, 1]])


class TestStringGrid:
    """reference StringGrid/StringCluster/FingerPrintKeyer (core/util)."""

    def test_fingerprint_keyer(self):
        from deeplearning4j_tpu.utils.string_grid import FingerPrintKeyer

        k = FingerPrintKeyer()
        assert k.key("Two words") == k.key("WORDS two!")
        assert k.key("  Café  ") == "cafe"
        assert k.key("a b a") == "a b"  # uniquified + sorted

    def test_string_cluster(self):
        from deeplearning4j_tpu.utils.string_grid import StringCluster

        c = StringCluster(["McDonalds", "mcdonalds", "McDonalds", "Burger"])
        clusters = c.get_clusters()
        assert len(c) == 2
        assert clusters[0] == {"McDonalds": 2, "mcdonalds": 1}
        assert c.canonical("mcdonalds") == "McDonalds"

    def _grid(self):
        from deeplearning4j_tpu.utils.string_grid import StringGrid

        return StringGrid(",", ["a,1,x", "b,2,y", "a,3,", "c,2,z"])

    def test_grid_io_and_columns(self, tmp_path):
        from deeplearning4j_tpu.utils.string_grid import StringGrid

        g = self._grid()
        assert len(g) == 4
        assert g.get_column(0) == ["a", "b", "a", "c"]
        path = str(tmp_path / "grid.csv")
        g.write_lines_to(path)
        g2 = StringGrid.from_file(path, ",")
        assert g2.to_lines() == g.to_lines()

    def test_row_and_column_surgery(self):
        g = self._grid()
        g.remove_rows_with_empty_column(2)
        assert len(g) == 3
        g.select(1, "2")
        assert len(g.select(1, "2")) == 2
        g.sort_by(1)
        assert [r[1] for r in g.rows] == ["1", "2", "2"]
        g.swap(0, 1)
        assert g.rows[0][1] == "a"
        g.remove_columns(2)
        assert g.num_columns == 2
        g.prepend_to_each("<", 0)
        g.append_to_each(">", 0)
        assert g.rows[0][0] == "<1>"

    def test_split_and_merge(self):
        from deeplearning4j_tpu.utils.string_grid import StringGrid

        g = StringGrid(",", ["a|b,1", "c|d,2"])
        g.split(0, "|")
        assert g.num_columns == 3
        assert g.rows[0] == ["a", "b", "1"]
        g.merge(0, 1)
        assert g.rows[0] == ["ab", "1"]

    def test_duplicates_and_primary_key(self):
        g = self._grid()
        dupes = g.get_rows_with_duplicate_values_in_column(0)
        assert len(dupes) == 2
        by_key = g.map_by_primary_key(0)
        assert len(by_key["a"]) == 2

    def test_similarity_filtering(self):
        from deeplearning4j_tpu.utils.string_grid import StringGrid

        g = StringGrid(",", ["kitten,kitten", "kitten,dog"])
        close = g.get_all_with_similarity(0.9, 0, 1)
        assert len(close) == 1
        g.filter_by_similarity(0.9, 0, 1)
        assert len(g) == 1

    def test_dedupe_by_cluster(self):
        from deeplearning4j_tpu.utils.string_grid import StringGrid

        g = StringGrid(",", ["McDonalds,1", "mcdonalds,2",
                             "McDonalds,3", "KFC,4"])
        g.dedupe_by_cluster(0)
        assert g.get_column(0) == ["McDonalds", "McDonalds",
                                   "McDonalds", "KFC"]


class TestInterop:
    """MLLibUtil.java parity: DataSet <-> numpy/torch/jax/LabeledPoint."""

    def _ds(self):
        from deeplearning4j_tpu.datasets.api import DataSet
        rng = np.random.RandomState(0)
        f = rng.rand(6, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[[0, 1, 2, 1, 0, 2]]
        return DataSet(f, y)

    def test_numpy_round_trip(self):
        from deeplearning4j_tpu.utils import interop
        ds = self._ds()
        f, y = interop.to_numpy(ds)
        ds2 = interop.from_numpy(f, y)
        np.testing.assert_array_equal(ds2.features, ds.features)
        np.testing.assert_array_equal(ds2.labels, ds.labels)
        import pytest
        with pytest.raises(ValueError, match="rows"):
            interop.from_numpy(f, y[:3])

    def test_torch_round_trip_shares_memory(self):
        import torch

        from deeplearning4j_tpu.utils import interop
        ds = self._ds()
        tf, ty = interop.to_torch(ds)
        assert isinstance(tf, torch.Tensor) and tf.shape == (6, 4)
        ds2 = interop.from_torch(tf, ty)
        np.testing.assert_array_equal(ds2.features, ds.features)
        # zero-copy is BEST-EFFORT: it holds for contiguous host numpy
        # arrays (this case); non-contiguous/device arrays get copied
        tf[0, 0] = 42.0
        assert np.asarray(ds.features)[0, 0] == 42.0
        from deeplearning4j_tpu.datasets.api import DataSet
        nc = DataSet(np.ones((4, 6), np.float32).T, np.eye(6, 3,
                                                           dtype=np.float32))
        tf2, _ = interop.to_torch(nc)
        tf2[0, 0] = 7.0
        assert nc.features[0, 0] == 1.0  # copy: no write-through

    def test_jax_device_arrays(self):
        import jax

        from deeplearning4j_tpu.utils import interop
        f, y = interop.to_jax(self._ds())
        assert isinstance(f, jax.Array) and f.shape == (6, 4)

    def test_labeled_points_round_trip(self):
        import pytest

        from deeplearning4j_tpu.utils import interop
        ds = self._ds()
        pts = interop.to_labeled_points(ds)
        assert [p[0] for p in pts] == [0, 1, 2, 1, 0, 2]
        ds2 = interop.from_labeled_points(pts, num_labels=3)
        np.testing.assert_array_equal(ds2.features, ds.features)
        np.testing.assert_array_equal(ds2.labels, ds.labels)
        with pytest.raises(ValueError, match="outside"):
            interop.from_labeled_points([(5, [1.0])], num_labels=3)
        with pytest.raises(ValueError, match="no labeled points"):
            interop.from_labeled_points([], num_labels=3)


class TestJaxEnv:
    """utils/jaxenv.py: the compile-cache and platform rules every entry
    point applies. Run in fresh interpreters — both rules are about
    what is true BEFORE JAX starts."""

    @staticmethod
    def _run(code, **env):
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        full = {k: v for k, v in os.environ.items()
                if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
        full.update(env)
        out = subprocess.run([sys.executable, "-c", code], cwd=repo,
                             env=full, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        return out.stdout.strip().splitlines()

    def test_cache_dir_is_taken_from_outside_or_fixed_in_the_checkout(self):
        import os

        code = ("import os, jax; from deeplearning4j_tpu.utils import jaxenv; "
                "jaxenv.configure(); "
                "print(os.environ['JAX_COMPILATION_CACHE_DIR']); "
                "print(jax.config.jax_compilation_cache_dir); "
                "print(os.environ['JAX_PLATFORMS'])")
        # set from outside: used as is, nothing else set in code
        assert self._run(code, JAX_COMPILATION_CACHE_DIR="/x",
                         JAX_PLATFORMS="cpu") == ["/x", "/x", "cpu"]
        # unset: one fixed path inside the checkout, exported for the
        # children; and with libtpu installed the platform is pinned so
        # a missing chip cannot become a quiet CPU run
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fixed = os.path.join(repo, ".jax_program_cache")
        assert self._run(code) == [fixed, fixed, "tpu,cpu"]

    def test_control_plane_pin_keeps_a_process_off_the_accelerator(self):
        """`tpu,cpu` fails loudly here (no chip), so reaching the CPU
        devices proves the TPU back end was never tried."""
        code = ("from deeplearning4j_tpu.utils import jaxenv; "
                "jaxenv.keep_off_accelerator(); import jax, os; "
                "print(jax.devices()[0].platform); "
                "print(os.environ['JAX_PLATFORMS'])")
        assert self._run(code, JAX_PLATFORMS="tpu,cpu") == ["cpu", "tpu,cpu"]
