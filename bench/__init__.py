"""On-chip benchmark of the TPU lossy-compression layer (see PERF.md)."""
