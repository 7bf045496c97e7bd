"""Traffic kinds: the general generators that traffic files name.

Each module has ``run(ctx) -> record``; the record holds what the
metric readers read (set-up and window walls, host spans, the reduced
trace, the device block) and the compared numbers (`checks`).
"""
