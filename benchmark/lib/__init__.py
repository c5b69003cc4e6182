"""The benchmark's own yardstick: manifest, traffic, weights, the plain
reference, metric arithmetic and the trace reduction. Nothing here is
imported by the program, and the reference imports nothing of it."""
