"""``chip_smoke.py`` rehearsed on the CPU, so the script cannot rot between
chip runs.

Its phase functions run here at N=1024, tile 128, in f32 with x64 off and
the Pallas kernels in interpret mode -- the same calls, checks and
thresholds the chip run makes at N=32768. Only ``main()`` insists on a TPU:
it must refuse the CPU, and a copy of the script standing alone (no
``src/repro`` beside it) must fail without printing a result.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

CFG = cs.SmokeConfig(n=1024, tile=128, r_max=128, impl="interpret",
                     requests=12, slots=4)


@pytest.fixture(autouse=True)
def _f32():
    # the chip path runs with x64 off; the suite's conftest turns it on
    with jax.enable_x64(False):
        yield


@pytest.fixture(scope="module")
def problem():
    with jax.enable_x64(False):
        K = cs.build_problem(CFG)
        ref = cs.dense_reference(K)
        op = cs.compress(K, CFG)
    assert K.dtype == op.dtype == jax.numpy.float32
    return K, ref, op


@pytest.fixture(scope="module")
def left(problem):
    _, _, op = problem
    with jax.enable_x64(False):
        return op.cholesky(cs.chol_options(CFG))


def test_compress_phase(problem):
    K, _, op = problem
    assert cs.check_compress(op, K, CFG) <= cs.COMPRESS_ERR_MAX


@pytest.mark.parametrize("algo", ["left", "right", "ldlt"])
def test_factor_phase(problem, left, algo):
    K, ref, op = problem
    if algo == "left":
        fact = left
    elif algo == "right":
        fact = op.cholesky(cs.chol_options(CFG, algo="right"))
    else:
        fact = op.ldlt(cs.chol_options(CFG))
    assert fact.L.U.dtype == jax.numpy.float32
    errs = cs.check_factor(algo, fact, K, ref, CFG, cs.LEFT_LOGDET_REL_MAX
                           if algo == "left" else cs.LOGDET_REL_MAX)
    assert errs["backward_err_1"] <= cs.BACKWARD_ERR_MAX


def test_pallas_matches_ref_factor(problem, left):
    _, _, op = problem
    import dataclasses

    fref = op.cholesky(dataclasses.replace(cs.chol_options(CFG), impl="ref"))
    assert cs.factor_distance(left, fref, CFG) <= cs.FACTOR_REL_DIFF_MAX


def test_sample_phase(problem, left):
    _, ref, _ = problem
    assert abs(cs.check_sample(left, ref, CFG) - 1.0) <= cs.SAMPLE_VAR_TOL


def test_serve_phase(problem, left):
    _, _, op = problem
    out = cs.serve(left, op, CFG)
    assert out["completed"] == CFG.requests


def test_main_refuses_without_tpu(capsys):
    assert jax.default_backend() != "tpu"
    assert cs.main([]) != 0
    assert "needs a TPU" in capsys.readouterr().err


def test_script_alone_fails_without_result(tmp_path):
    script = tmp_path / "chip_smoke.py"
    script.write_text((ROOT / "chip_smoke.py").read_text())
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, cwd=tmp_path, timeout=120)
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
