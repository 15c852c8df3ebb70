"""A percentile of one of the runner's own host-clock samples."""

from benchmarks import loadgen


def read(data, sample, q):
    return loadgen.percentile(data.get("samples", {}).get(sample, []), q)
