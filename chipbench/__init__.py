"""On-chip benchmark of the trainer: cells, configurations, traffic,
metric readers and plain references, found by name (see run.py)."""
