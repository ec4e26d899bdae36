"""``create_sharded_state`` (parameters from the seed on the device, then the
optimizer state), fenced, on the host's clock."""
LAYER, UNIT, SOURCE, MOVES = "trainer", "s", "host_clock", "setup_s"


def read(run):
    return run.init_state_s or None
