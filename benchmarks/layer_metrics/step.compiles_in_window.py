"""Executables built or loaded inside the timed window; should be 0."""
LAYER, UNIT, SOURCE, MOVES = "step", "count", "program_counter", \
    "tokens_per_s_per_chip"


def read(run):
    return run.compile_window.get("compiles")
