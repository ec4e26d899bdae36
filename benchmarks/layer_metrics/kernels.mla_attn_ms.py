"""Per step, the summed device time of the splash kernel's Mosaic calls on
the first chip in a latent-attention cell (each call causal over a row, the
q.k head ``qk_head_dim`` wide and the v head ``v_head_dim``):
``kernels.splash_ms``'s reading.  None where the configuration has no
``kv_lora_rank``."""
from benchmarks.lib import spec

LAYER, UNIT, SOURCE, MOVES = "kernels", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"

_splash_ms = spec.load_module("layer_metrics", "kernels.splash_ms")


def read(run):
    if "kv_lora_rank" not in run.cell["config_file"]:
        return None
    return _splash_ms.read(run)
