"""The benchmark's own float64 references."""
