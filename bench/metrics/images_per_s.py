"""Images whose responses the client received in the window, over the
window's wall time (first submission to the end of the last wave)."""
from bench.stats import images_per_s as read  # noqa: F401
