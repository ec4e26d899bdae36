"""The least time the chip could take for the splash calls of a
latent-attention cell (each call's FLOPs over the causal half at the model's
own two head dimensions, QK^T over ``qk_head_dim`` and PV over
``v_head_dim``, and its bytes, q and k at the one and v and o at the other,
``lib/cost_joyai.py``, over the peaks table, whatever the kernel pads; a call
whose name holds ``fwd`` is a forward, any other a fused backward) over the
time they took.  ``describe`` says which peak bounds each kind of call."""
from benchmarks.lib import cost_joyai

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", \
    "tokens_per_s_per_chip"


def _least(run, event):
    return cost_joyai.attention_least_time(
        "fwd" if "fwd" in event.name else "bwd", run.cell["config_file"],
        run.attention_batch_per_chip, run.seq_len, run.peaks.flops,
        run.peaks.hbm_bw)


def read(run):
    events = run.kernel_events("splash")
    if not events or run.peaks is None \
            or "kv_lora_rank" not in run.cell["config_file"]:
        return None
    return 100.0 * sum(_least(run, e)[0] for e in events) \
        / sum(e.dur for e in events)


def describe(run):
    if run.peaks is None or "kv_lora_rank" not in run.cell["config_file"]:
        return None
    return {("fwd" if "fwd" in e.name else "bwd") + "_bound_by":
            _least(run, e)[1] for e in run.kernel_events("splash")}
