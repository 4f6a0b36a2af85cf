"""Median of the engine's wave execution time (``wave_seconds``) over
the window's waves, in the open-loop cells."""
from bench.stats import wave_ms as read  # noqa: F401
