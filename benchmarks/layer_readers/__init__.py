"""Readers of per-layer metrics that are files of their own: one module a
reader, named as a metric's file (`benchmarks/layer_metrics/<name>.json`)
names it, with a `read(args, ctx)` that returns a number, or None where it
finds nothing to read. `benchmarks/readers.py` says what `ctx` holds."""
