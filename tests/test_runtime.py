"""Runtime fault-tolerance tests: step watchdog EWMA clamping, SIGTERM
preemption handling (install/uninstall/context-manager), and elastic
re-meshing plans (``runtime.fault`` / ``runtime.elastic``, pure Python), and
where ``runtime.compile_cache`` points JAX's compile cache."""
import os
import signal
from pathlib import Path

from repro.runtime.elastic import plan_elastic_remesh
from repro.runtime.fault import FaultTolerantLoop, StepWatchdog


class FakeCkpt:
    def __init__(self):
        self.saves = []
        self.waited = False

    def save(self, step, state, blocking=False):
        self.saves.append((step, blocking))

    def wait(self):
        self.waited = True


# --- watchdog ----------------------------------------------------------------

def test_watchdog_first_observation_seeds_ewma():
    wd = StepWatchdog(threshold=2.0, alpha=0.1)
    assert wd.observe(0, 5.0) is False
    assert wd.ewma == 5.0 and wd.straggler_steps == []


def test_watchdog_flags_straggler_and_clamps_ewma():
    """A 100x spike is flagged, but enters the average clamped to
    threshold*ewma — one straggler must not poison the baseline."""
    wd = StepWatchdog(threshold=2.0, alpha=0.1)
    wd.observe(0, 1.0)
    assert wd.observe(1, 100.0) is True
    assert wd.straggler_steps == [1]
    assert wd.ewma == 0.9 * 1.0 + 0.1 * 2.0          # clamped at 2x, not 100
    # the next normal step is NOT flagged against a poisoned average
    assert wd.observe(2, 1.0) is False


def test_watchdog_tracks_gradual_slowdown():
    """A gradual 1.5x drift is absorbed into the EWMA without flags."""
    wd = StepWatchdog(threshold=2.0, alpha=0.5)
    for i, dt in enumerate((1.0, 1.2, 1.4, 1.5)):
        assert wd.observe(i, dt) is False
    assert wd.ewma > 1.0


# --- preemption / SIGTERM lifecycle ------------------------------------------

def test_sigterm_uninstall_restores_previous_handler():
    """Regression: ``install_sigterm`` used to leak the handler forever —
    uninstall (and the context manager) must restore the prior disposition."""
    sentinel = lambda signum, frame: None     # noqa: E731
    prev = signal.signal(signal.SIGTERM, sentinel)
    try:
        loop = FaultTolerantLoop(FakeCkpt())
        loop.install_sigterm()
        assert signal.getsignal(signal.SIGTERM) is not sentinel
        loop.uninstall_sigterm()
        assert signal.getsignal(signal.SIGTERM) is sentinel
        loop.uninstall_sigterm()              # idempotent
        assert signal.getsignal(signal.SIGTERM) is sentinel
        with FaultTolerantLoop(FakeCkpt()):
            assert signal.getsignal(signal.SIGTERM) is not sentinel
        assert signal.getsignal(signal.SIGTERM) is sentinel
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_preemption_triggers_final_blocking_checkpoint():
    """A SIGTERM mid-run flips the flag; the loop stops at the step
    boundary and writes one final *blocking* checkpoint."""
    ckpt = FakeCkpt()
    with FaultTolerantLoop(ckpt, save_every=100) as loop:
        def step_fn(state, batch):
            if state == 2:
                os.kill(os.getpid(), signal.SIGTERM)
            return state + 1, {}

        state, step, _ = loop.run(0, step_fn, lambda s: {}, start_step=0,
                                  total_steps=50)
    assert loop.preempted and step < 50
    assert ckpt.saves and ckpt.saves[-1][1] is True    # blocking final save
    assert ckpt.waited


def test_clean_run_saves_periodically_no_final_blocking():
    ckpt = FakeCkpt()
    loop = FaultTolerantLoop(ckpt, save_every=2)
    state, step, wd = loop.run(0, lambda s, b: (s + 1, {}), lambda s: {},
                               start_step=0, total_steps=6)
    assert step == 6 and state == 6 and not loop.preempted
    assert ckpt.saves == [(2, False), (4, False), (6, False)]
    assert ckpt.waited


# --- elastic re-meshing ------------------------------------------------------

def test_elastic_full_mesh_passthrough():
    plan = plan_elastic_remesh(256, model_axis=16, old_data_axis=16)
    assert plan.mesh_shape == (16, 16)
    assert plan.axis_names == ("data", "model")
    assert plan.microbatch_scale == 1
    assert plan.note == "full mesh healthy"
    multi = plan_elastic_remesh(512, model_axis=16, old_data_axis=16, pods=2)
    assert multi.mesh_shape == (2, 16, 16)
    assert multi.axis_names == ("pod", "data", "model")


def test_elastic_halves_data_axis_preserving_global_batch():
    """Losing chips halves the data axis; microbatch_scale compensates so
    the global batch (and training dynamics) are unchanged."""
    plan = plan_elastic_remesh(200, model_axis=16, old_data_axis=16)
    assert plan.mesh_shape == (8, 16)          # 128 <= 200 < 256
    assert plan.microbatch_scale == 2
    assert "degraded" in plan.note
    quarter = plan_elastic_remesh(70, model_axis=16, old_data_axis=16)
    assert quarter.mesh_shape == (4, 16) and quarter.microbatch_scale == 4
    # the product data*scale always preserves the global batch
    for n in (256, 200, 130, 70, 40, 17):
        p = plan_elastic_remesh(n, model_axis=16, old_data_axis=16)
        data = p.mesh_shape[-2]
        assert data * p.microbatch_scale == 16
        assert data * 16 <= n


def test_elastic_returns_none_when_model_axis_cannot_fit():
    assert plan_elastic_remesh(15, model_axis=16, old_data_axis=16) is None
    assert plan_elastic_remesh(0, model_axis=8, old_data_axis=4) is None


# --- compile cache -----------------------------------------------------------

def test_compile_cache_honours_env_then_checkout_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache goes
    to the fixed, git-ignored ``.jax_cache/`` at the checkout root."""
    import jax

    from repro.runtime import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
