"""Seconds of the train step's first call (span ``train.first_call``: trace,
lower, compile or persistent-cache load, dispatch), from the first-call
record the program's ``TrainStep`` left in its compile registry
(``ray_tpu.util.device_telemetry``)."""
import sys

LAYER, UNIT, SOURCE, MOVES = "step", "s", "program_span", "setup_s"


def read(run):
    telemetry = sys.modules.get("ray_tpu.util.device_telemetry")
    first_calls = getattr(telemetry, "first_calls", None)
    calls = first_calls("train_step") if first_calls else []
    return calls[0]["seconds"] if calls else None
