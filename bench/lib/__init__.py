"""The benchmark's harness: registry, the system under test, trace reduction, work, check."""
