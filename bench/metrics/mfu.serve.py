"""Model FLOP/s utilization of the forecast rollout step
(``models/weathermixer.forecast_step`` as ``ForecastEngine`` runs it):
forward FLOPs of the occupied batch slots (each request holds a slot for
as many steps as its lead), over the device time of the rollout-step
programs (``jit__step`` in the trace) times the chips' bf16 peak.  Padded
slots do work that does not count."""
from harness import flops

STEP_PROGRAM = r"^jit__step\b"


def read(run):
    if run.trace is None:
        return None
    spent = run.trace.module_time(STEP_PROGRAM)
    slot_steps = run.counts.get("slot_steps", 0)
    if spent <= 0 or not slot_steps:
        return None
    work = flops.forward_flops(run.config) * slot_steps
    return 100.0 * work / (spent * run.chips * run.peak["bf16_flops_per_s"])
