"""Seconds jax spent building or loading executables during set-up
(``/jax/core/compile/backend_compile_duration`` events)."""
LAYER, UNIT, SOURCE, MOVES = "step", "s", "program_counter", "setup_s"


def read(run):
    return run.compile_setup.get("compile_s")
