"""The benchmark harness: spec discovery, loops, reference, trace reduction."""
