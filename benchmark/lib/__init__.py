"""The benchmark's harness: window, trace reduction, comparison."""
