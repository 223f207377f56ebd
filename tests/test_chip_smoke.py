"""chip_smoke.py's phases at smoke widths on the CPU.

The script itself refuses any device but a TPU; its phase functions are
rehearsed here with the smoke config, Pallas kernels interpreted, so a
change that breaks the script's control flow fails without a chip.
"""

import importlib.util
import os
import subprocess
import sys

import jax
import pytest

from repro.config import get_smoke_config
from repro.models.model import build_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def granite():
    cfg = get_smoke_config("granite-3-8b")
    return cfg, jax.jit(build_model(cfg).init)(jax.random.key(0))


def test_refuses_to_run_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert out.stdout == ""


def test_cut_config_keeps_every_width(smoke):
    from repro.config import get_config
    full, cut = get_config("granite-3-8b"), smoke.cut_config()
    assert cut.num_layers == smoke.DEPTH < full.num_layers
    assert cut.param_dtype == "bfloat16"
    for f in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size", "probe"):
        assert getattr(cut, f) == getattr(full, f), f


def test_kernel_phase_interpreted(smoke, granite, monkeypatch):
    monkeypatch.setattr(smoke, "MAX_LEN", 256)
    monkeypatch.setattr(smoke, "CHUNK", 32)
    errs = smoke.kernel_phase(granite[0], seed=0, interpret=True)
    assert set(errs) == {"paged_decode_attention",
                         "paged_decode_attention_multi[T=4]",
                         "paged_decode_attention_multi[T=32]"}


def test_model_phase_kernels_on_matches_off(smoke, granite, monkeypatch):
    monkeypatch.setattr(smoke, "BATCH", 4)
    monkeypatch.setattr(smoke, "MAX_LEN", 256)
    monkeypatch.setattr(smoke, "CHUNK", 128)
    got = smoke.model_phase(*granite, seed=0, require_custom_call=False)
    assert got["logit_err"] <= smoke.LOGIT_REL_TOL * got["logit_scale"]


def test_serve_phase_preempts_and_finishes(smoke, granite):
    cfg, params = granite
    traffic = smoke.make_traffic(cfg, seed=0)
    assert len(traffic) == smoke.N_REQUESTS
    assert all(64 <= len(p) <= 512 and 16 <= n <= 128 for p, n in traffic)
    got = smoke.serve_phase(cfg, params, traffic, use_kernels=False)
    assert got["preemptions"] >= 1
    assert [len(t) for t in got["tokens"]] == [n for _, n in traffic]
