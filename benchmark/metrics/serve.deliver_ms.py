"""Mean delivery of the served batches over the window (ms from a
batch's outputs ready to its last future resolved: fetch, id
translation, slicing and the callbacks), from the program's
``serve.deliver_ms`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.deliver_ms")
