"""What the layers' checkpoints keep for the backward beyond their inputs,
the splash residuals and the routing, a chip: ``remat_kept_bytes`` of the
train step's first-call record (``ray_tpu/ops/remat.py``: the rungs the rule
climbed, each for as many layers as fit the chip's free bytes), in GiB.  0
where the rule found no room; None where no layer asks the rule (GPT-2) or
the program notes no such count.  It is what ``step.recompute_ms`` falls by
and ``device.peak_hbm`` rises by.  ``describe``: the rungs as the record
lists them (a program before PR 63: names alone; since: [name, layers that
keep it, layers that name it]) and the room the rule saw."""
import sys

LAYER, UNIT, SOURCE, MOVES = "step", "GiB", "program_counter", \
    "tokens_per_s_per_chip"


def _record(run):
    """The last first-call record of the train step that holds the rule's
    decision, or None."""
    telemetry = sys.modules.get("ray_tpu.util.device_telemetry")
    if not hasattr(telemetry, "first_calls"):
        return None
    records = [r for r in telemetry.first_calls("train_step")
               if r.get("remat_kept_bytes") is not None]
    return records[-1] if records else None


def read(run):
    record = _record(run)
    return None if record is None else record["remat_kept_bytes"] / 2 ** 30


def describe(run):
    record = _record(run)
    return record and {key: record.get(key) for key in (
        "remat_kept", "remat_room_bytes", "remat_routing_bytes")}
