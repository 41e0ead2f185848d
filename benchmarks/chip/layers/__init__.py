"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json.  ``read(ctx)`` returns the number, or None when the
run gave it nothing to read (the harness then leaves the metric out)."""
