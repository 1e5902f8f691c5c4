"""Mean fill of the served batches over the window (rows / bucket rows),
from the program's ``serve.batch_fill_ratio`` histogram."""

from benchmark.obs_read import histogram_mean


def read(run):
    return histogram_mean(run.obs, "serve.batch_fill_ratio")
