"""Per step, the summed device time of the grouped-matmul kernel's Mosaic
calls on the first chip (the megablox ``gmm`` and ``tgmm``) in a cell whose
expert layers hold a share of the experts: the kernels visit the tiles of the
held groups only, so this is the time of the rows really routed here.  The
reading is ``kernels.experts_ms``'s, by the instruction names the compiled
step and the trace share; ``describe`` gives the time by call."""
from benchmarks.lib import spec

LAYER, UNIT, SOURCE, MOVES = "kernels", "ms/step", "device_trace", \
    "tokens_per_s_per_chip"

_whole_layer = spec.load_module("layer_metrics", "kernels.experts_ms")
read, describe = _whole_layer.read, _whole_layer.describe
