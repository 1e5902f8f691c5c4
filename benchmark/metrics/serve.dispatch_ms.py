"""Mean host dispatch of the served batches over the window (ms from a
batch's drain to its search enqueued on the device: pin, filter
snapshot, side buffer, padding, upload and the call), from the
program's ``serve.dispatch_ms`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.dispatch_ms")
