"""The benchmark harness: cells, the epoch loop, the trace reduction, the
plain reference and the comparison."""
