"""What decides `correct`, driven as a run drives it, at a size a test
run can hold. The harness's look for a chip is skipped and the rest of a
run is the real one.

- a sound run comes out correct, in each driver;
- the control (the reference in the program's place, computed in fp8,
  the step below the bfloat16 the configuration states) fails at least
  one of a cell's numbers;
- with the timed path broken underneath, `correct` comes out false, once
  for each fault these cells can have: a step that returns its state
  unchanged, half of the batch left out with the mean over the rest, a
  token altered where it is produced. (None of the cells exchanges
  anything between chips.)

The limits here are the tiny cells' own (`tiny.py`); the real cells'
were read on the chip at their own size (PERF.md section 2)."""

import pytest

from benchmark import manifest, run
from tests.benchmark_suite import tiny

SEED = 2 ** 31 + 2401
SECONDS = 0.6


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


def drive(root, name, **kw):
    cell = manifest.load_cell(name, root)
    return run.execute(cell, SEED, SECONDS, False, require_chip=False,
                       **kw)


def over(line, prefix=""):
    """The compared numbers that pass their limit, of the run itself or
    of a control (`prefix`)."""
    out = []
    for name, c in line["compared"].items():
        value = line["numbers"][prefix + name] if prefix else c["value"]
        if value is None or not value <= c["limit"]:
            out.append(name)
    return out


@pytest.mark.parametrize("name", ["tiny.tiny-closed", "tiny.tiny-open"])
def test_sound_serving_run_is_correct(root, name):
    line = drive(root, name)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["numbers"]["tokens_compared"] >= 20
    assert list(line)[-1] == "compared"
    assert over(line) == []
    assert set(line["metrics"]) >= {"setup_s", "itl_p98_ms"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_serving_control_in_fp8_is_not_correct(root):
    """The reference put in the program's place: it decodes greedily in
    float32 (what a sound server would have served), and at the same
    positions the fp8 reading of the same weights puts other tokens
    first, by more than the limit; the bfloat16 reading, the precision
    the configuration states, stays within it."""
    from benchmark import check

    cell = manifest.load_cell("tiny.tiny-closed", root)
    sample = tiny.greedy_sample(cell, SEED, 40, 24, 16)
    numbers = check.serve_numbers(cell, SEED, sample, ("fp8", "bf16"))
    limit = cell.limits["token_gap_max"]
    assert numbers["tokens_compared"] == 40 * 16
    assert numbers["token_gap_max"] == 0.0
    assert numbers["control_bf16_token_gap_max"] <= limit
    assert numbers["control_fp8_token_gap_max"] > 1.5 * limit, numbers
    assert check.verdict(
        {"token_gap_max": numbers["control_fp8_token_gap_max"]},
        cell.limits)["correct"] is False


def test_sound_training_run_is_correct_and_its_control_is_not(root):
    line = drive(root, "tiny.tiny-train", control_modes=("fp8",))
    assert line["correct"] is True, line
    assert over(line) == []
    assert over(line, "control_fp8_"), line["numbers"]
    assert set(line["metrics"]) == {"setup_s", "train_tok_s"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    import jax

    from deeplearning4j_tpu.models import transformer

    def broken(cfg, lr=1e-2):
        loss = jax.jit(lambda p, t: transformer.lm_loss(p, t, cfg))
        return lambda p, v, t: (p, v, loss(p, t))

    monkeypatch.setattr(transformer, "make_train_step", broken)
    line = drive(root, "tiny.tiny-train")
    assert line["correct"] is False
    failing = over(line)
    assert "grad_norm_gap_worst_leaf" in failing
    assert "change_norm_gap_worst_leaf" in failing
    # by the measure in use a state left unchanged reads 1
    assert line["compared"]["grad_norm_gap_worst_leaf"]["value"] == \
        pytest.approx(1.0, abs=1e-6)


def test_half_the_batch_left_out_is_not_correct(root, monkeypatch):
    from deeplearning4j_tpu.models import transformer

    real = transformer.make_train_step

    def broken(cfg, lr=1e-2):
        step = real(cfg, lr)
        return lambda p, v, t: step(p, v, t[: t.shape[0] // 2])

    monkeypatch.setattr(transformer, "make_train_step", broken)
    line = drive(root, "tiny.tiny-train")
    assert line["correct"] is False
    assert "grad_norm_gap_worst_leaf" in over(line)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    from deeplearning4j_tpu.serving import decode_loop

    real = decode_loop.GenerationStream._emit
    seen = {"n": 0}

    def broken(self, token):
        seen["n"] += 1
        if seen["n"] % 5 == 0:
            token = (int(token) + 1) % tiny.CONFIG["vocab_size"]
        real(self, token)

    monkeypatch.setattr(decode_loop.GenerationStream, "_emit", broken)
    line = drive(root, "tiny.tiny-closed")
    assert line["correct"] is False
    assert over(line) == ["token_gap_max"]
