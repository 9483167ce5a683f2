"""Reader kinds for per-layer metrics.  `benchmarks/layer_metrics/<name>.json`
names one (`"reader": <kind>`); `read(spec, run)` returns the value or None
where it finds nothing to read (the metric is then left out of the line).
A new kind is a new module here, not an edit."""
