"""95th percentile over all requests of the window of response received
minus scheduled arrival; a request not served counts as missing any
limit."""
from bench.stats import latencies_ms, pct


def read(run):
    return pct(latencies_ms(run), 95)
