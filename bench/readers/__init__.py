"""One reader per source of per-layer numbers.  ``read(metric, ctx)`` takes a
layer metric's data file (bench/layer_metrics/<name>.json) and the run's
context and returns a number, or None when there is nothing to read."""
