"""The ``q``-th percentile, in ms, of the durations of the program's
spans named ``span`` over the traced window; the sample count and the
samples beyond the percentile are on an earlier line
(``span_percentile``).  A median hides what is rare and long (150 ms
admission scans in 150 of 1,900 waves, PR 38): this is for those."""

from benchmarks import loadgen, program_trace


def read(data, span, q):
    values = [(n["end"] - n["start"]) / 1e6
              for n in program_trace.span_forest(data["trace"])
              if n["name"] == span]
    if not values:
        program_trace.missing(data, "span_percentile", [span])
        return None
    harness = data.get("harness")
    if harness is not None:
        harness.log(line="span_percentile", span=span, q=q,
                    samples=len(values),
                    samples_beyond=int(len(values) * (100 - q) / 100),
                    median_ms=loadgen.percentile(values, 50),
                    max_ms=max(values))
    return loadgen.percentile(values, q)
