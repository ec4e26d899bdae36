"""Per step, the summed device time of the splash kernel's Mosaic calls on
the first chip in a block-diffusion cell (each call over a row's 2S
positions, the noised and the clean copy, under the three-part block mask):
``kernels.splash_ms``'s reading.  None where the configuration has no
``block_length``."""
from benchmarks.lib import spec

LAYER, UNIT, SOURCE, MOVES = "kernels", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"

_splash_ms = spec.load_module("layer_metrics", "kernels.splash_ms")


def read(run):
    if "block_length" not in run.cell["config_file"]:
        return None
    return _splash_ms.read(run)
