"""CLI tests (reference TrainConfigTest / BaseSubCommandTest — but the
reference Train.exec() was an empty stub; these test actual execution)."""

import json

import numpy as np
import pytest

from deeplearning4j_tpu.cli import main
from deeplearning4j_tpu.config import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.iris import load_iris


@pytest.fixture()
def iris_csv(tmp_path):
    x, y = load_iris()
    data = np.hstack([np.asarray(x), np.argmax(np.asarray(y), 1)[:, None]])
    path = tmp_path / "iris.csv"
    np.savetxt(path, data, delimiter=",", fmt="%.4f")
    return str(path)


@pytest.fixture()
def iris_features_csv(tmp_path):
    x, _ = load_iris()
    path = tmp_path / "iris_features.csv"
    np.savetxt(path, np.asarray(x), delimiter=",", fmt="%.4f")
    return str(path)


@pytest.fixture()
def conf_json(tmp_path):
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .num_iterations(20).use_adagrad(False)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    path = tmp_path / "conf.json"
    path.write_text(conf.to_json())
    return str(path)


def test_train_test_predict_round_trip(tmp_path, iris_csv,
                                       iris_features_csv, conf_json,
                                       capsys):
    ckpt = str(tmp_path / "model.ckpt")
    assert main(["train", "-i", iris_csv, "-m", conf_json, "-o", ckpt,
                 "--epochs", "5"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["saved"] == ckpt and out["score"] < 1.0

    assert main(["test", "-i", iris_csv, "-m", ckpt]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    metrics = json.loads(lines[-1])
    assert metrics["f1"] > 0.7

    preds_path = str(tmp_path / "preds.csv")
    assert main(["predict", "-i", iris_features_csv, "-m", ckpt,
                 "-o", preds_path]) == 0
    preds = np.loadtxt(preds_path)
    assert preds.shape[0] == 150
    assert set(np.unique(preds)) <= {0.0, 1.0, 2.0}


def test_predict_to_stdout(iris_features_csv, conf_json, tmp_path, capsys):
    # fresh (untrained) net from conf json also works for predict
    assert main(["predict", "-i", iris_features_csv, "-m", conf_json]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 150


def test_train_without_labels_errors(tmp_path, conf_json, capsys):
    path = tmp_path / "x.csv"
    np.savetxt(path, np.random.rand(5, 4), delimiter=",")
    assert main(["train", "-i", str(path), "-m", conf_json,
                 "-o", str(tmp_path / "m.ckpt"),
                 "--label-columns", "0"]) == 2


def test_missing_required_flag_exits():
    with pytest.raises(SystemExit):
        main(["train", "-i", "x.csv"])  # no --model/--output


def test_predict_with_labelled_csv(tmp_path, iris_csv, conf_json, capsys):
    """predict honors --label-columns so a labelled train/test CSV can be
    reused; without it, a clear width-mismatch message (not a jax shape
    error) and exit 2."""
    out_path = str(tmp_path / "preds.txt")
    assert main(["predict", "-i", iris_csv, "-m", conf_json,
                 "-o", out_path, "--label-columns", "1"]) == 0
    assert len(open(out_path).read().splitlines()) == 150
    assert main(["predict", "-i", iris_csv, "-m", conf_json,
                 "-o", out_path]) == 2
    assert "label-columns" in capsys.readouterr().err


def test_train_with_checkpoint_dir_and_inspect(tmp_path, iris_csv,
                                               conf_json, capsys):
    """--checkpoint-dir writes sharded async autosaves during the fit;
    `checkpoint inspect` prints the manifest; `-m <dir>` loads the
    latest committed step for test/predict/serve."""
    from deeplearning4j_tpu.checkpoint import list_steps

    ckpt = str(tmp_path / "model.ckpt")
    ckdir = str(tmp_path / "autosaves")
    assert main(["train", "-i", iris_csv, "-m", conf_json, "-o", ckpt,
                 "--epochs", "3", "--checkpoint-dir", ckdir]) == 0
    capsys.readouterr()
    # arrays-path fit ticks per epoch: 3 committed autosaves
    assert list_steps(ckdir) == [1, 2, 3]

    # inspect: human output carries the manifest summary + leaf table
    assert main(["checkpoint", "inspect", ckdir]) == 0
    out = capsys.readouterr().out
    assert '"step": 3' in out and "params__0__W" not in out
    assert "params/0/W" in out

    # machine output round-trips as one JSON object with the leaf table
    assert main(["checkpoint", "inspect", ckdir, "--json",
                 "--step", "2"]) == 0
    summary = json.loads(capsys.readouterr().out.strip())
    assert summary["step"] == 2 and summary["steps"] == [1, 2, 3]
    leaves = {row["leaf"] for row in summary["leaves"]}
    assert "params/0/W" in leaves
    assert summary["total_bytes"] > 0

    # the checkpoint DIRECTORY is a valid -m for test (latest step)
    assert main(["test", "-i", iris_csv, "-m", ckdir]) == 0
    metrics = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert 0.0 <= metrics["accuracy"] <= 1.0


def test_checkpoint_inspect_missing_dir_errors(tmp_path, capsys):
    assert main(["checkpoint", "inspect",
                 str(tmp_path / "nothing")]) == 2
    assert "no committed" in capsys.readouterr().err


def test_checkpoint_every_without_dir_refuses(tmp_path, iris_csv,
                                              conf_json, capsys):
    """--checkpoint-every with nowhere to put autosaves must refuse
    loudly, not run a fit the user believes is checkpointed."""
    assert main(["train", "-i", iris_csv, "-m", conf_json,
                 "-o", str(tmp_path / "m.ckpt"),
                 "--checkpoint-every", "2"]) == 2
    assert "--checkpoint-dir" in capsys.readouterr().err


# ----------------------------------------------------- ISSUE 9: resume
def test_train_resume_auto_discovers_latest_committed(tmp_path, iris_csv,
                                                      conf_json, capsys):
    """`--resume auto` restores params+updater+cursor from the newest
    COMMITTED step under --checkpoint-dir without naming the step dir,
    and continues the run with the autosave numbering extended."""
    from deeplearning4j_tpu.checkpoint import format as ckfmt

    ck = str(tmp_path / "ck")
    assert main(["train", "-i", iris_csv, "-m", conf_json,
                 "-o", str(tmp_path / "m1.ckpt"), "--epochs", "1",
                 "--batch-size", "50", "--checkpoint-dir", ck]) == 0
    capsys.readouterr()
    first_steps = ckfmt.list_steps(ck)
    assert first_steps, "first run committed nothing"
    assert main(["train", "-i", iris_csv, "-m", conf_json,
                 "-o", str(tmp_path / "m2.ckpt"), "--epochs", "2",
                 "--batch-size", "50", "--checkpoint-dir", ck,
                 "--resume", "auto"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    resumed = json.loads(lines[0])
    assert resumed["resuming"] == ck
    assert resumed["step"] == first_steps[-1]
    summary = json.loads(lines[-1])
    assert summary["resumed_from"] == first_steps[-1]
    # the resumed run's autosaves EXTEND the numbering (no collision)
    assert ckfmt.list_steps(ck)[-1] > first_steps[-1]


def test_train_resume_auto_torn_only_dir_lists_candidates(
        tmp_path, iris_csv, conf_json, capsys):
    import os

    from deeplearning4j_tpu.checkpoint import format as ckfmt

    ck = str(tmp_path / "torn")
    step_dir = os.path.join(ck, ckfmt.step_dir_name(4))
    os.makedirs(step_dir)
    with open(os.path.join(step_dir, ckfmt.MANIFEST), "w") as f:
        f.write("{}")
    assert main(["train", "-i", iris_csv, "-m", conf_json,
                 "-o", str(tmp_path / "m.ckpt"), "--batch-size", "50",
                 "--checkpoint-dir", ck, "--resume", "auto"]) == 2
    err = capsys.readouterr().err
    assert "step_0000000004" in err and "torn" in err


def test_train_resume_auto_without_checkpoint_dir_refuses(
        tmp_path, iris_csv, conf_json, capsys):
    assert main(["train", "-i", iris_csv, "-m", conf_json,
                 "-o", str(tmp_path / "m.ckpt"),
                 "--resume", "auto"]) == 2
    assert "--checkpoint-dir" in capsys.readouterr().err


@pytest.mark.elastic
def test_train_elastic_smoke(tmp_path, iris_csv, capsys, monkeypatch):
    """`train --elastic N` drives the TrainingSupervisor end to end
    from the CLI: N spawned workers, every job folded, model saved.
    The supervisor process is control plane: it pins itself to the
    host CPU before it builds anything and never asks JAX for its
    devices — on a TPU host they belong to the workers."""
    import jax

    from deeplearning4j_tpu.utils import jaxenv

    def refuse(*a, **kw):
        raise AssertionError("the supervisor asked JAX for its devices")

    monkeypatch.setattr(jax, "devices", refuse)
    monkeypatch.setattr(jax, "local_devices", refuse)
    pinned = []
    monkeypatch.setattr(jaxenv, "keep_off_accelerator",
                        lambda: pinned.append(jax.config.jax_platforms))
    conf = (NeuralNetConfiguration.builder()
            .lr(0.1).n_in(4).activation_function("tanh")
            .optimization_algo("iteration_gradient_descent")
            .num_iterations(2).use_adagrad(False).momentum(0.0)
            .list(2).hidden_layer_sizes([8])
            .override(1, layer="output", loss_function="mcxent",
                      activation_function="softmax", n_out=3)
            .pretrain(False).build())
    conf_path = tmp_path / "econf.json"
    conf_path.write_text(conf.to_json())
    out_path = str(tmp_path / "elastic.ckpt")
    assert main(["train", "-i", iris_csv, "-m", str(conf_path),
                 "-o", out_path, "--elastic", "2", "--epochs", "1",
                 "--batch-size", "50",
                 "--checkpoint-dir", str(tmp_path / "eck"),
                 "--run-timeout", "240"]) == 0
    summary = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["saved"] == out_path
    assert summary["workers"] == 2
    assert summary["folded"] == summary["jobs"] == 3  # ceil(150/50)
    assert summary["respawns"] == 0
    assert pinned, "the supervisor did not keep itself off the accelerator"
