"""Mean service time of the served batches over the window (ms from a
batch's dispatch to its answers on the host), from the program's
``serve.batch_latency_ms`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.batch_latency_ms")
