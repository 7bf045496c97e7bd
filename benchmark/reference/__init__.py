"""The plain reference: Handel written afresh in numpy, one seed and one
simulated millisecond at a time, on the host.

`world.py` holds the seed's draws, node placement, the nodes that are
down and the latency model; `sim.py` the mailbox and the protocol in both
modes; `cities.json` the reference's city table.  It imports nothing of
the program and takes nothing the program has made: no code, tables or
state.  Its semantics are the ones the configurations state, and its
docstrings say them in words.
"""
