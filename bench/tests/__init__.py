"""The benchmark's own tests (CPU, small sizes; not part of tier-1)."""
