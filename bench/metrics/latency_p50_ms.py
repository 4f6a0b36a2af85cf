"""Median over all requests of the window of response received minus
scheduled arrival."""
from bench.stats import latencies_ms, pct


def read(run):
    return pct(latencies_ms(run), 50)
