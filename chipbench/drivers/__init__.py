"""Drivers: what one step of a cell calls, and how its result is checked."""
