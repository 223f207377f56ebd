"""End-to-end behaviour tests for the paper's system (TRAIL pipeline):
train a tiny LM -> harvest embeddings -> train probe -> serve with the real
probe under SPRPT-LP, validating the paper's *relative* claims at CPU scale.
Also: the dry-run entry point lowers+compiles on the production mesh
(subprocess so the 512-device XLA flag never leaks into this process)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.config import INPUT_SHAPES, get_config, get_smoke_config, shape_applies
from repro.models.model import Model
from repro.serving.engine import run_policy
from repro.serving.predictors import OraclePredictor, ProbePredictor
from repro.serving.workload import WorkloadConfig, generate
from repro.training import optimizer as opt_mod
from repro.training.data import DataConfig, batches, harvest_probe_data
from repro.training.train import (ProbeTrainConfig, probe_mae, train_lm,
                                  train_probe)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def trained_system():
    cfg = get_smoke_config("trail-llama")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    dc = DataConfig(vocab=cfg.vocab_size, seq_len=96, batch=8,
                    prompt_mean=10, max_out=60, seed=0)
    ocfg = opt_mod.AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=80)
    params, _, _ = train_lm(model, params, batches(dc, 80), ocfg, 80)
    taps, rem = harvest_probe_data(
        model, params, DataConfig(vocab=cfg.vocab_size, seq_len=96, batch=8,
                                  prompt_mean=10, max_out=60, seed=9), 8)
    probe_params, _ = train_probe(taps, rem, cfg.probe, cfg.d_model,
                                  ProbeTrainConfig(epochs=6))
    params = dict(params)
    params["probe"] = probe_params
    return cfg, model, params, (taps, rem)


def test_probe_beats_prompt_only_mae(trained_system):
    """Paper Figure 3's relative claim: tap-embedding probe beats a
    prompt-only (BERT-regime) predictor on remaining-length MAE."""
    cfg, model, params, (taps, rem) = trained_system
    mae_probe = probe_mae(params["probe"], taps, rem, cfg.probe)
    # prompt-only baseline: same head trained on the *embedding-table mean*
    # (no forward pass, no per-iteration refresh) — the S^3/BERT regime
    emb = np.asarray(params["embed"], np.float32)
    rng = np.random.default_rng(0)
    # crude prompt-only features: mean embedding of random prompt tokens
    feats = emb[rng.integers(16, cfg.vocab_size, size=(len(rem), 8))].mean(1)
    bert_params, _ = train_probe(feats, rem, cfg.probe, cfg.d_model,
                                 ProbeTrainConfig(epochs=6))
    mae_bert = probe_mae(bert_params, feats, rem, cfg.probe)
    assert mae_probe < mae_bert


def test_full_pipeline_trail_beats_fcfs(trained_system):
    cfg, model, params, _ = trained_system
    wc = WorkloadConfig(n_requests=10, request_rate=60.0, seed=4,
                        vocab=cfg.vocab_size, prompt_mean=8.0,
                        out_median=8.0, max_out=24)
    reqs = generate(wc)
    results = {}
    for pol in ("fcfs", "trail"):
        pred = ProbePredictor(cfg.probe, probe_params=params["probe"],
                              embed_table=params["embed"])
        s = run_policy(cfg, pol, reqs, max_batch=3, mode="real",
                       model=model, params=params, predictor=pred)
        results[pol] = s.summary()
        assert len(s.latencies) == len(reqs)
    assert results["trail"]["mean_ttft"] <= results["fcfs"]["mean_ttft"] * 1.1


def test_long_500k_skip_rules():
    shape = INPUT_SHAPES["long_500k"]
    runs = {a: shape_applies(get_config(a), shape)
            for a in ("mamba2-370m", "hymba-1.5b", "gemma3-1b", "gemma2-9b",
                      "granite-3-8b", "qwen1.5-32b", "arctic-480b",
                      "olmoe-1b-7b", "whisper-tiny", "paligemma-3b")}
    assert runs["mamba2-370m"] and runs["hymba-1.5b"]
    assert runs["gemma3-1b"] and runs["gemma2-9b"]
    assert not any(runs[a] for a in ("granite-3-8b", "qwen1.5-32b",
                                     "arctic-480b", "olmoe-1b-7b",
                                     "whisper-tiny", "paligemma-3b"))


@pytest.mark.slow
def test_dryrun_lowers_on_production_mesh():
    """Subprocess: the smallest (arch, shape) pair must lower+compile on the
    256-chip mesh via the real entry point."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-370m",
         "--shape", "long_500k", "--out", "/tmp/dryrun_test"],
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")},
        capture_output=True, text=True, timeout=520)
    assert out.returncode == 0, out.stdout + out.stderr
    with open("/tmp/dryrun_test/mamba2-370m_long_500k_16x16.json") as f:
        rep = json.load(f)
    assert rep["roofline"]["n_chips"] == 256
    assert rep["memory"]["peak_per_device_gb"] < 16.0


def test_serve_cli_invalid_flags_exit_2():
    """The serve CLI's contract for bad input: exit code 2 (argparse's
    convention) with a one-line error on stderr — never a traceback,
    never status 1."""
    cases = [
        ["--rate-scale", "2.0"],                     # needs --trace
        ["--trace", "/nonexistent/t.jsonl"],         # unreadable path
        ["--chaos", "crash:1@5"],                    # needs --replicas >= 2
        ["--chaos", "meteor:0@5", "--replicas", "2"],  # bad fault kind
        ["--policy", "nope"],                        # argparse choice error
        ["--admission-control"],                     # needs watermark
        ["--age-boost", "-1"],                       # negative knob
        ["--deadline-slack", "5"],                   # needs --deadline
        ["--port", "8100"],                          # needs --serve
        ["--serve", "--port", "99999"],              # port out of range
        ["--time-scale", "20"],                      # needs --serve
        ["--serve", "--time-scale", "0"],            # must be positive
        ["--clients", "0"],                          # must be positive
        ["--think-time", "1.0"],                     # needs --clients
        ["--clients", "4", "--think-time", "-1"],    # negative think time
        ["--requests-per-client", "2"],              # needs --clients
        ["--serve", "--trace", "sample"],            # serve is closed-loop
        ["--clients", "4", "--disagg", "1:3"],       # no disagg front door
        ["--serve", "--replicas", "2"],              # single engine only
        ["--serve", "--metrics-out", "m.json"],      # GET /metrics instead
    ]
    for argv in cases:
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", *argv],
            env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")},
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 2, (argv, out.returncode, out.stderr)
        assert "error:" in out.stderr, (argv, out.stderr)
        assert "Traceback" not in out.stderr, (argv, out.stderr)


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, a real-mode serve run writes its
    compiled programs there (and the code sets no other directory)."""
    cache = tmp_path / "jax-cache"
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "trail-llama",
         "--smoke", "--real", "--n", "2", "--kv-layout", "paged"],
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
             "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(cache),
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache
    assert CHECKOUT_CACHE_DIR == Path(ROOT).resolve() / ".jax_cache"
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == was     # nothing set
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_oracle_predictor_statistics():
    """Sharper probe temp -> lower serving latency (prediction quality
    matters, the paper's TRAIL vs TRAIL-BERT axis)."""
    cfg = get_config("granite-3-8b")
    wc = WorkloadConfig(n_requests=150, request_rate=14.0, seed=5,
                        vocab=cfg.vocab_size)
    reqs = generate(wc)
    good = run_policy(cfg, "trail", reqs, mode="sim", seed=6,
                      predictor=OraclePredictor(cfg.probe, temp=0.3,
                                                flip_prob=0.0, seed=6))
    bad = run_policy(cfg, "trail", reqs, mode="sim", seed=6,
                     predictor=OraclePredictor(cfg.probe, temp=5.0,
                                               flip_prob=0.5, bert_sigma=2.0,
                                               seed=6))
    assert good.summary()["mean_latency"] <= bad.summary()["mean_latency"] * 1.05
