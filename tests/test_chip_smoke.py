"""``chip_smoke.py`` on the CPU: the script refuses to run without a
TPU, and its phases — the same functions, at the reduced
``seesaw-150m`` widths with the Pallas kernels in interpret mode — pass
their own checks."""
import importlib.util
import math
import os

import pytest

from repro.configs.seesaw_paper import SEESAW_150M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

MODEL = SEESAW_150M.reduced()
SEQ = 64


def test_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert "no TPU chip found" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture(scope="module")
def xla_run():
    return cs.train(MODEL, seq_len=SEQ)


def test_train_phase_ramp_and_executables(xla_run):
    tr, hist, _ = xla_run
    losses = cs.check_training("train/xla", tr, hist, MODEL.vocab_size,
                               min_drop=0.0)
    assert [r["batch_size"] for r in hist] == [32] * 16 + [64] * 4
    assert len(tr.engine._cache) == 2
    assert abs(losses[0] - math.log(MODEL.vocab_size)) < 1.0


def test_pallas_phase_tracks_xla(xla_run):
    _, hist, _ = xla_run
    tr, got, _ = cs.train(MODEL, seq_len=SEQ, backend="pallas_interpret",
                          max_steps=cs.PALLAS_STEPS)
    losses = cs.check_training("train/pallas", tr, got, MODEL.vocab_size,
                               min_drop=0.0)
    assert len(losses) == cs.PALLAS_STEPS
    cs.check_band("train/pallas", losses, [r["loss"] for r in hist])


def test_kernel_checks_pass_in_interpret_mode():
    errs = cs.kernel_checks(MODEL, SEQ, "pallas_interpret", batch=1)
    assert set(errs) == {"flash_fwd", "flash_grad", "rmsnorm_fwd",
                         "rmsnorm_grad"}


def test_serve_phase_matches_reference_loop(capsys):
    cs.phase_serve(MODEL, cs.CompileLog(), prompt_len=(8, 40),
                   backend="pallas_interpret")
    out = capsys.readouterr().out
    assert f"tokens_equal={cs.N_REQUESTS * cs.MAX_NEW}/" in out
    assert "decode_executables=1" in out


def test_band_check_rejects_drift():
    with pytest.raises(cs.PhaseFailed):
        cs.check_band("x", [10.0, 9.0], [10.0, 9.0 + 2 * cs.LOSS_BAND])
