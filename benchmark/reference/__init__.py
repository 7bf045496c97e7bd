"""The plain references: each protocol written afresh in numpy, one seed
and one simulated millisecond at a time, on the host.

`benchmark/check.py` loads every module here by path and takes, for a
cell, the one module whose ``PROTOCOL`` names the cell's protocol (the
registry class name of its `ScenarioSpec`).  Such a module declares:

  PROTOCOL      the registry class name it simulates
  NODE_FIELDS   the engine's per-node counters compared
  PROTO_FIELDS  the protocol state's per-node counters compared
  PROTO_LOSSES  the protocol state's per-seed loss scalars (a sound run
                loses nothing: the engine's dropped and clamped, and
                these, stay 0)
  TINY          parameter overrides that cut a configuration to the CPU
                tests' size
  tables(evaluate)
                what the reference needs evaluated on the cell's device
                (``evaluate(fn, *arrays)`` runs ``fn(xp, *arrays)``
                there), or None
  trajectory(params, seed, boundaries, tables)
                per boundary ms, a dict of the compared fields, the
                clock (``time``), ``dropped``, ``clamped`` and the
                nodes that are ``down``

Handel's is `sim.py`, with `world.py` (the seed's draws, node
placement, the nodes that are down, the latency model) and
`cities.json` (the city table).  A reference imports nothing of the
program and takes nothing the program has made: no code, tables or
state.  Its semantics are the ones the configurations state, and its
docstrings say them in words.
"""
