"""End-to-end readers: ``read(window)`` gives one number from the
host clock."""
