"""On-device generators of benchmark inputs, one module per matrix family."""
