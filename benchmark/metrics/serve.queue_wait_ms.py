"""Mean linger of the served batches over the window (ms from the first
request's enqueue to the batch's drain), from the program's
``serve.queue_wait_ms`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.queue_wait_ms")
