"""On-chip benchmark of the L-PCN engine and server (see README.md)."""
