"""all-gather + all-reduce + reduce-scatter instructions in the compiled
per-device step (an async pair counts once)."""
LAYER, UNIT, SOURCE, MOVES = "collectives", "count", "program_counter", \
    "tokens_per_s_per_chip"


def read(run):
    if run.hlo is None:
        return None
    c = run.hlo["collectives"]
    return c["all-gather"] + c["all-reduce"] + c["reduce-scatter"]
