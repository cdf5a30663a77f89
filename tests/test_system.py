"""End-to-end system tests: train a reduced model (loss must drop), resume
from checkpoint, serve batched requests, and a subprocess mini dry-run that
exercises the production sharding rules on 8 host devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def test_train_loss_decreases(tmp_path):
    from repro.launch.train import main

    history = main([
        "--arch", "qwen2-0.5b", "--preset", "smoke", "--steps", "100",
        "--batch", "8", "--seq", "64", "--lr", "1e-2",
        "--ckpt-dir", str(tmp_path), "--log-every", "2",
    ])
    losses = [l for _, l in history]
    assert len(losses) >= 10
    # synthetic zipfian stream: the model learns the unigram head; from the
    # ln(512)~6.2-nat start this reliably sheds >1 nat in 100 steps
    assert losses[-1] < losses[0] * 0.8, (losses[0], losses[-1])


def test_train_resume(tmp_path):
    from repro.launch.train import main

    main(["--arch", "qwen2-0.5b", "--preset", "smoke", "--steps", "10",
          "--batch", "4", "--seq", "32", "--save-every", "5",
          "--ckpt-dir", str(tmp_path)])
    # second invocation resumes from step 10 checkpoint
    h = main(["--arch", "qwen2-0.5b", "--preset", "smoke", "--steps", "14",
              "--batch", "4", "--seq", "32", "--save-every", "5",
              "--ckpt-dir", str(tmp_path), "--log-every", "1"])
    steps = [s for s, _ in h]
    assert min(steps) >= 10, steps


def test_train_microbatched_matches_shape(tmp_path):
    from repro.launch.train import main

    h = main(["--arch", "olmoe-1b-7b", "--preset", "smoke", "--steps", "6",
              "--batch", "8", "--seq", "32", "--microbatches", "2",
              "--ckpt-dir", str(tmp_path), "--log-every", "1"])
    assert len(h) >= 3
    assert all(np.isfinite(l) for _, l in h)


def test_serve_driver():
    """Continuous batching: 5 requests through 2 slots needs slot-freeing;
    accounting must be per-request (exactly 5 served, no dead-slot tokens)."""
    from repro.launch.serve import main

    stats = main(["--arch", "qwen2-0.5b", "--requests", "5", "--batch", "2",
                  "--prompt-len", "16", "--max-new", "4"])
    assert stats["served"] == 5
    # each request's FIRST token comes out of the prefill wave; the rest are
    # decode steps — the split must be exact, not rounded up to batches
    assert stats["prefill_tokens"] == 5
    assert stats["decode_tokens"] == 5 * 4 - 5
    assert stats["generated_tokens"] == 5 * 4
    assert stats["prefills"] >= 3                # joins actually happened
    assert [len(c) for c in stats["completions"]] == [4] * 5
    # every requested technology got a simulated-clock report
    for tech in ("afmtj", "mtj", "cpu"):
        rep = stats["device"][tech]
        assert rep["sim_time_s"] > 0 and rep["energy_j"] > 0
        assert rep["ttft_p99_s"] >= rep["ttft_p50_s"] > 0


def test_serve_honors_eos():
    """A sequence emitting --eos-id frees its slot early and stops counting."""
    from repro.launch.serve import main

    probe = main(["--arch", "qwen2-0.5b", "--requests", "2", "--batch", "2",
                  "--prompt-len", "16", "--max-new", "4"])
    eos = probe["completions"][0][0]             # deterministic first token
    stats = main(["--arch", "qwen2-0.5b", "--requests", "2", "--batch", "2",
                  "--prompt-len", "16", "--max-new", "4", "--eos-id", str(eos)])
    assert stats["completions"][0] == [eos]      # finished at the EOS token
    assert stats["decode_tokens"] < probe["decode_tokens"]


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Lower+compile a real cell pipeline on 8 host devices in a subprocess
    (the full 512-device sweep runs via repro.launch.dryrun --all)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs.base import ShapeConfig
from repro.configs.registry import smoke_config
from repro.launch import sharding as SH, steps as ST
from repro.models import model as M

cfg = smoke_config("qwen3-8b")
mesh = jax.make_mesh((4, 2), ("data", "model"))
shape = ShapeConfig("train_4k", "train", 64, 8, microbatches=2)
SH.activation_policy(mesh, cfg, shape)
ap = M.abstract_params(cfg)
ps = SH.param_shardings(cfg, mesh, M.logical_axes(cfg), ap)
batch = ST.input_specs(cfg, shape)
bs = SH.batch_shardings(mesh, shape, batch)
fn = ST.make_train_step(cfg, shape)
aopt = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32), ap)
jit = jax.jit(fn, in_shardings=(ps, ps, ps, None, bs),
              out_shardings=(ps, ps, ps, None, None), donate_argnums=(0,1,2))
c = jit.lower(ap, aopt, aopt, jax.ShapeDtypeStruct((), jnp.int32), batch).compile()
print("COMPILED", c.cost_analysis()["flops"] > 0)
"""
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")},
                       capture_output=True, text=True, timeout=300)
    assert "COMPILED True" in r.stdout, r.stderr[-2000:]


def test_dryrun_results_valid():
    """Validate any dry-run artifacts produced so far (full table checked in
    EXPERIMENTS.md; this guards the schema + fit-in-HBM for completed cells)."""
    d = REPO / "results" / "dryrun"
    files = list(d.glob("*.json")) if d.exists() else []
    if not files:
        pytest.skip("no dry-run artifacts yet — results/dryrun/*.json are "
                    "produced by the TPU dry-run workflow (ROADMAP.md); "
                    "this test validates them when present")
    for f in files:
        r = json.loads(f.read_text())
        assert r["cost"]["flops"] > 0, f.name
        assert r["memory"]["temp_size_in_bytes"] is not None
        coll = r["collectives"]
        assert set(coll) == {"all-reduce", "all-gather", "reduce-scatter",
                             "all-to-all", "collective-permute"}
