"""The benchmark's harness: window, trace reduction, the chain plane's
plain reference."""
