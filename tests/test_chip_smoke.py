"""chip_smoke.py on the CPU: its leg functions driven at a tiny size
(the Pallas kernels through the interpreter, set by the spec), its
checks shown to fire, and the command itself shown to refuse a machine
without a TPU. The real run is `python3 chip_smoke.py` on the chip."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

LM = {"vocab_size": 64, "d_model": 32, "n_heads": 2, "n_layers": 2,
      "d_ff": 64, "max_len": 256, "seed": 0, "interpret": True}
MLP = (16, 8, 10)


@pytest.fixture(scope="module")
def legs():
    """Both legs, once, tiny: prompts on both sides of the 128-token
    flash threshold, the paged kernel forced through the interpreter,
    the train block at T=128 so the flash kernels are in the program."""
    serve = chip_smoke.serve_leg(
        lm=LM, mlp=MLP, prompt_lens=(20, 40, 150), new_tokens=6,
        platform="cpu", timeout=300,
        serve_args=("--decode-kernel", "pallas", "--slots", "2"))
    train = chip_smoke.train_leg(
        lm=LM, batch=2, tokens_per_row=129, steps=3, ref_prompt_len=20,
        ref_new_tokens=6, mlp=MLP, mlp_batch=16, platform="cpu",
        timeout=300)
    return serve, train


def test_serve_leg_answers_and_counts(legs):
    serve, _ = legs
    assert serve["device"]["platform"] == "cpu"
    assert serve["kernel"] == "pallas"
    assert serve["stats"]["decode_step_programs"] == 1
    assert serve["stats"]["requests"] == 7
    assert serve["prefix_hits"] >= 2        # the long AND the short repeat
    assert len(serve["first_tokens"]) == 6


def test_train_leg_steps_and_names_its_device(legs):
    _, train = legs
    assert train["device"]["platform"] == "cpu"
    assert train["losses"][-1] < train["losses"][0]
    assert train["tokens"] == [2, 129]
    # conftest's eight virtual devices: the data-parallel leg ran
    assert set(train["four_chip"]) == {"DataParallelTrainer",
                                       "ShardedUpdateTrainer"}


def test_served_tokens_match_the_full_recompute_reference(legs):
    """main()'s cross-leg check: paged cache + flash prefill + paged
    kernel against transformer_logits recomputed from scratch."""
    serve, train = legs
    assert serve["first_tokens"] == train["reference_tokens"]


def test_t1023_trap_fails_the_train_leg():
    """A (B, 128) token batch trains at T=127: no tile divides it, both
    passes take blockwise, and the leg must say so instead of timing
    the wrong program."""
    with pytest.raises(chip_smoke.SmokeFailure, match="blockwise"):
        chip_smoke.train_leg(
            lm=LM, batch=2, tokens_per_row=128, steps=2, ref_prompt_len=5,
            ref_new_tokens=2, mlp=MLP, mlp_batch=16, platform="cpu",
            timeout=300)


def test_wrong_device_is_a_failure_not_a_smaller_run():
    with pytest.raises(chip_smoke.SmokeFailure, match="not 'tpu'"):
        chip_smoke.check_device(
            {"platform": "cpu", "kind": "cpu", "count": 1}, "tpu", "child")
    with pytest.raises(chip_smoke.SmokeFailure, match="did not name"):
        chip_smoke.check_device(None, "tpu", "child")


def test_command_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "NO TPU HERE" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_parent_never_imports_jax():
    code = ("import sys, chip_smoke; "
            "chip_smoke.cache_state(0, 1); chip_smoke.mlp_conf((4, 3, 2)); "
            "bad = [m for m in sys.modules if m == 'jax' "
            "or m.startswith(('jax.', 'jaxlib', 'deeplearning4j_tpu'))]; "
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=60)
    # and nothing before the train child's own function imports it
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        parent = f.read().split("def _train_child(", 1)[0]
    assert "import jax" not in parent
