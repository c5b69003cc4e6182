"""What decides ``correct``, shown to fail: the int8 control at a size a
test run can hold, and a whole run of the harness (minus its look for a
chip) with the timed path broken underneath."""

import pathlib

import numpy as np
import pytest

from benchmark import run
from benchmark.lib import manifest as mf
from benchmark.lib import reference

HERE = pathlib.Path(__file__).resolve().parent
REHEARSAL = HERE / "rehearsal" / "manifest.json"


def test_the_int8_control_stands_apart_from_bfloat16():
    """Teacher-forced over 384 positions at the control test's widths:
    the token that int8 weights put first lies, on average, over three
    times as far below the float32 reference's best as the token that
    bfloat16 arithmetic puts first. The cells' limit on ``gap_mean``
    sits between their two readings on the chip (PERF.md, section 2);
    this keeps the separation itself under test."""
    cfg = mf.load_json(HERE / "rehearsal" / "debug-control.json")
    tokens = np.random.default_rng(5).integers(1, cfg["vocab_size"], 384)
    sound = reference.first_choice_gaps(11, cfg, tokens, "bfloat16").mean()
    control = reference.first_choice_gaps(11, cfg, tokens, "int8").mean()
    assert control > 3 * sound
    assert control > 0


def run_cell(workload):
    return run.main(["--workload", workload, "--seed", "5", "--seconds", "3",
                     "--trace", "0"], allow_cpu=True, manifest_path=REHEARSAL)


def test_serve_run_is_correct_and_an_altered_token_is_not(monkeypatch):
    assert run_cell("debug-serve.debug_chat")["correct"] is True

    # break the decode program's output where it is produced: every
    # row's tokens come back shifted by one id
    from paddle_tpu.inference import serving
    sound_init = serving.DecodeEngine.__init__

    def init_then_break(engine, *args, **kwargs):
        sound_init(engine, *args, **kwargs)
        inner = engine._decode

        def broken(*a):
            toks, *pool = inner(*a)
            return ((toks + 1) % engine._cfg.vocab_size, *pool)

        engine._decode = broken

    monkeypatch.setattr(serving.DecodeEngine, "__init__", init_then_break)
    assert run_cell("debug-serve.debug_chat")["correct"] is False


def test_train_run_is_correct_and_a_frozen_step_is_not(monkeypatch):
    assert run_cell("debug-train.debug_dp2_mp2")["correct"] is True

    # a train step that reports a loss and returns its state as it was
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    monkeypatch.setattr(
        dist, "DistTrainStep",
        lambda *a, **k: lambda x, y: paddle.to_tensor(np.float32(6.2)))
    with pytest.raises(Exception):
        # a step that never ran leaves the optimizer without state: the
        # harness cannot even read a gradient norm, which is a failure
        run_cell("debug-train.debug_dp2_mp2")
