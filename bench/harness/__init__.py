"""The GEE chip benchmark's harness: spec lookup, traffic, reference, trace reduction."""
