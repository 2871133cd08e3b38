"""chip_smoke.py's phases at a smoke size on the CPU: the script's own
checks (exact 2:4, float64 reference sweep, continuous serving from
packed weights, kernel and logit parity) must pass here before they are
spent on a chip.  The device phase and the compiled-kernel check need a
TPU and are exercised only by the script itself."""

import dataclasses
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.configs import get_smoke  # noqa: E402
from repro.serve import sparsify_params  # noqa: E402


@pytest.fixture(scope="module")
def smoke_model():
    # the bring-up model's family at smoke width, in the published
    # config's bf16 so the tolerances face the same rounding
    cfg = dataclasses.replace(get_smoke("qwen1_5_0_5b"), dtype="bfloat16")
    return chip_smoke.build_model(cfg)


@pytest.fixture(scope="module")
def pruned(smoke_model):
    model, params = smoke_model
    return chip_smoke.prune(model, params, n_calib=8, calib_len=32,
                            blocksize=32, ref_rows=16)


def test_prune_checks(pruned):
    assert pruned is not None


def test_serve_from_packed(smoke_model, pruned):
    model, _ = smoke_model
    creqs = chip_smoke.make_requests(model.cfg.vocab_size,
                                     prompt_lens=(20, 33, 9), max_new=6)
    out = chip_smoke.serve(model, sparsify_params(pruned), creqs,
                           max_len=64, max_batch=2, want_kernels=False)
    assert [len(r.tokens) for r in out] == [6, 6, 6]


def test_paged_parity(smoke_model):
    model, _ = smoke_model
    chip_smoke.paged_parity(model.cfg, batch=2, max_len=64)


def test_logit_parity(smoke_model, pruned):
    model, _ = smoke_model
    prompt = np.arange(3, 40, dtype=np.int32) % model.cfg.vocab_size
    chip_smoke.logit_parity(model, pruned, sparsify_params(pruned), prompt,
                            max_len=64)


def test_refuses_without_tpu():
    """No TPU here: the script must fail, print no result and never fall
    back to the CPU (it sets ``jax_platforms`` itself)."""
    out = subprocess.run([sys.executable, str(chip_smoke.__file__)],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
