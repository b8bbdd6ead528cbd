"""One reader per per-layer metric: ``read(ctx)`` returns the value, or
None where the run holds nothing to read (an untraced run; the metric is
then left out). A traced run that lacks what a reader looks for raises
``benchmark.trace.NotFound``, and the run fails."""
