"""The benchmark harness: specs, data, traffic, references, trace reduction."""
