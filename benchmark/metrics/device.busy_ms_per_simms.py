"""device.busy_ms_per_simms: device busy time (the union of innermost op
intervals on the ops line, `benchmark/trace.py`) over the seed-sim-ms
the traced window advanced, in ms per seed-sim-ms."""


def read(record):
    t = record.get("trace")
    if not t or not t["busy_s"]:
        return None
    return 1000.0 * t["busy_s"] / record["window"]["seed_sim_ms"]
