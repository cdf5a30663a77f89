"""Hierarchical in-memory-computing architecture model (paper Sec. III/IV).

  hierarchy — L1/L2/main-memory AFMTJ subarray organization (CHIME-style)
  cpu_model — ARM Cortex-A72 analytical baseline (2 GHz, 32KB L1/1MB L2/8GB)
  workloads — the paper's six kernels as op traces (bnn, img-grayscale,
              img-threshold, mac, mat_add, rmse)
  evaluate  — system-level latency/energy vs the CPU baseline (Fig. 4)
  mapping   — beyond-paper: mapping LM-architecture inference onto the IMC
  write_margin — WER-targeted write-pulse sizing via the campaign engine
  write_path — stochastic write path: write-verify retry scheduler over
              thermal LLG transients, measured latency/energy/retry
              distributions and residual bit-error rates (DESIGN.md §7)
  analog_pipeline — functional analog MVM through the Pallas bitline/XNOR
              kernels: conductance programming, IR drop, signed ADC
              (DESIGN.md §6)
  read_path — read-disturb / retention / sense-margin scenario family
              through the fused campaign engine, measured read timings and
              the retention+disturb-derived refresh policy (DESIGN.md §10)
  model_analog — model-level analog accuracy: whole transformer forwards
              routed through the analog MVM via the linear-interception
              hook, fused fake-analog fast path + weight-programming cache
              (DESIGN.md §12)
  faults    — hard-fault injection: stuck-at / dead-line / endurance-wear
              defect planes via the counter-RNG (rates are data, not
              compile keys), repair policies (spare lines, pair masking,
              ECC) and CRN-paired degradation studies (DESIGN.md §13)
"""
from repro.imc.cpu_model import CPUModel, CORTEX_A72  # noqa: F401
from repro.imc.workloads import WORKLOADS, Workload  # noqa: F401

# Everything touching the circuit stack re-exports lazily (PEP 562): the
# hierarchy/evaluate chain imports JAX and the campaign engine pulls
# shard_map + Pallas — costs that JAX-free consumers (the serving
# scheduler/traffic/simulator stack, ``imc.cost_model`` at import time)
# must not pay at package-import time.
_HIERARCHY_EXPORTS = ("IMCHierarchy", "build_hierarchy")
_EVALUATE_EXPORTS = ("evaluate_system", "SystemResult")
_WRITE_MARGIN_EXPORTS = ("wer_margined_pulse",)
_ANALOG_EXPORTS = ("AnalogConfig", "AccuracyReport", "ProgrammedArray",
                   "analog_matmul", "binary_matmul", "mvm_accuracy",
                   "program_weights", "kernel_operands")
_WRITE_PATH_EXPORTS = ("WritePolicy", "ArrayWriteResult", "MeasuredWrite",
                       "WriteSurface", "write_verify", "program_bits",
                       "measured_write_timings", "write_surface",
                       "nominal_pulse")
_MODEL_ANALOG_EXPORTS = ("ModelAccuracyReport", "fake_analog_matmul",
                         "fake_kernel_operands",
                         "program_weights_cached", "programming_key",
                         "param_tree_hash", "model_forward_logits",
                         "analog_model_logits", "model_accuracy",
                         "model_accuracy_surface", "logit_metrics")
_FAULTS_EXPORTS = ("FaultSpec", "RepairPolicy", "REPAIR_NONE", "REPAIR_SPARE",
                   "REPAIR_SPARE_ECC", "REPAIR_POLICIES", "apply_repair",
                   "fault_code_plane", "column_ok_plane")
_READ_PATH_EXPORTS = ("ReadDisturbResult", "DisturbModel", "RetentionResult",
                      "SenseYieldResult", "SizedRead", "MeasuredRead",
                      "RefreshPolicy", "read_disturb_campaign",
                      "fit_disturb_model", "accumulated_disturb",
                      "reads_between_refresh", "retention_campaign",
                      "retention_horizons", "sense_margin_yield",
                      "size_read_drive", "measured_read_timings",
                      "derive_refresh_policy")


def __getattr__(name):
    if name in _HIERARCHY_EXPORTS:
        from repro.imc import hierarchy

        return getattr(hierarchy, name)
    if name in _EVALUATE_EXPORTS:
        from repro.imc import evaluate

        return getattr(evaluate, name)
    if name in _WRITE_MARGIN_EXPORTS:
        from repro.imc import write_margin

        return getattr(write_margin, name)
    if name in _ANALOG_EXPORTS:
        from repro.imc import analog_pipeline

        return getattr(analog_pipeline, name)
    if name in _WRITE_PATH_EXPORTS:
        from repro.imc import write_path

        return getattr(write_path, name)
    if name in _FAULTS_EXPORTS:
        from repro.imc import faults

        return getattr(faults, name)
    if name in _READ_PATH_EXPORTS:
        from repro.imc import read_path

        return getattr(read_path, name)
    if name in _MODEL_ANALOG_EXPORTS:
        from repro.imc import model_analog

        return getattr(model_analog, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
