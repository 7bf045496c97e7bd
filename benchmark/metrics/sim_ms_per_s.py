"""sim_ms_per_s: seed-sim-ms of every chunk completed in the window, over
the time from the window's start (the batch's init) to the last
completion.  Host clock."""


def read(record):
    w = record["window"]
    return w["seed_sim_ms"] / w["seconds"] if w["seconds"] > 0 else None
