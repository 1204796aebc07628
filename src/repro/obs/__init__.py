"""``repro.obs`` — run telemetry for COLA drivers.

Three layers, all opt-in (a telemetry-off run executes the exact pre-obs
program, bitwise):

* **On-device counters** (``obs.counters``): a ``Counters`` pytree carried
  through the round-block scan (``ColaConfig.telemetry=True``) accumulating
  per-round wire bytes and collective-permute counts (from the compiled
  plan's contract budget), quant saturation fraction and EF residual norm
  (``repro.core.quant``), and robust-gate edge rejections per sender
  (``repro.core.mixing.gate_flags`` — XLA CSEs the recomputed gate against
  the defended mix, so the counter is free). Totals land in every driver's
  ``history["telemetry"]``.
* **Host tracing** (``obs.trace``): ``span()`` names a host phase (env
  build, recorder set-up, schedule build, driver build, block dispatches,
  stop-flag syncs, history fetch, bench repeats) as a ``jax.profiler``
  annotation, the same timeline as a device profile; a scoped ``Tracer``
  also sums span timings and driver-cache hit/miss events
  (``executor.cache_listener``) for the run report. The device side of a
  round carries ``jax.named_scope`` names (``cola.mix``,
  ``cola.local_solve``, ``cola.update``, ``cola.record``, ...).
* **Run registry** (``obs.report``): telemetry runs append a ``RunReport``
  JSONL line under ``.repro_runs/`` (env ``REPRO_RUNS_DIR`` overrides);
  ``python -m repro.obs list|show|diff|timeline`` queries it.
"""
from repro.obs.counters import (Counters, init_counters, make_update,
                                round_increments, summarize)
from repro.obs.report import (RunReport, append_report, diff_reports,
                              load_reports, runs_file)
from repro.obs.trace import Tracer, current, span, use

__all__ = [
    "Counters", "RunReport", "Tracer", "append_report", "current",
    "diff_reports", "init_counters", "load_reports", "make_update",
    "round_increments", "runs_file", "span", "summarize", "use",
]
