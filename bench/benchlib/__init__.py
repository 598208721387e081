"""The chip benchmark's own library: cell lookup, traffic, serving windows,
trace reduction and the correctness comparison. It imports the program only
to drive it (`repro.runtime.gnn_server.GraphServe` and its scheduler); the
yardstick (graph generator, weights, reference, work counts, peaks) lives
here so that a change to the program cannot move it.
"""
