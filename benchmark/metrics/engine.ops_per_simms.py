"""engine.ops_per_simms: innermost device-op executions in the traced
window per lockstep simulated ms (a count: exact for a given program)."""


def read(record):
    t = record.get("trace")
    if not t or not t["ops"]:
        return None
    return t["ops"] / record["window"]["lockstep_ms"]
