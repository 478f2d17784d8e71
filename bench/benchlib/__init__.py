"""The benchmark harness: registry, generators, reference, trace reduction."""
