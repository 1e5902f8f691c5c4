"""Mean device wait of the served batches over the window (ms from a
batch's search enqueued to its outputs ready), from the program's
``serve.device_wait_ms`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.device_wait_ms")
