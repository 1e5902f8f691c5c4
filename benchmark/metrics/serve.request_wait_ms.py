"""Mean wait of the served requests over the window (ms from each
request's enqueue to the drain of its batch, one sample per request),
from the program's ``serve.request_wait_ms`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.request_wait_ms")
