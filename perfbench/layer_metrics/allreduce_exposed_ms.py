"""Per step, on one device: time in collective operations during which no
other operation ran on that device."""

import trace as tracemod


def read(window):
    if window.tables is None or len(window.tables.devices) < 2:
        return None
    prog = tracemod.programs(window.tables).get("jit_train_step")
    if not prog or not prog["count"]:
        return None
    exposed = tracemod.exposed_collective_seconds(window.tables)
    return 1e3 * exposed / prog["count"]
