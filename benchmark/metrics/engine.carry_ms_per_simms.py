"""engine.carry_ms_per_simms: device time of the chunk program under no
stage scope (``engine.carry``: loop control and XLA's scan-carry
copies; `benchmark/stages.py`) per seed-sim-ms of the traced window,
in ms.  None where the trace has no stage table (no accelerator plane,
or a program without stage scopes)."""


def read(record):
    stages = (record.get("trace") or {}).get("stages") or {}
    return stages.get("engine.carry") or None
