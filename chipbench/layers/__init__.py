"""Per-layer readers: ``read(trace, window)`` gives one number, or None
where the trace holds nothing for it."""
