"""Per chip, the fullest: ``bytes_in_use`` after the window plus the compiled
step's ``temp_size_in_bytes`` (``peak_bytes_in_use`` leaves a program's
temporaries out, PR 21)."""
LAYER, UNIT, SOURCE, MOVES = "device", "GiB", "program_counter", \
    "tokens_per_s_per_chip"


def read(run):
    in_use = [m.get("bytes_in_use") for m in run.memory]
    if not run.step_memory or not all(in_use):
        return None
    return (max(in_use) + run.step_memory["temp"]) / 2 ** 30
